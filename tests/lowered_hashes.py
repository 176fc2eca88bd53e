"""sha256 of the StableHLO text of the programs ``test_chip_compile.PROGRAMS``
lowers, from ONE tree's ``ray_tpu``: what a refactor that must not move a
program is held to (the same hashes from the parent's tree and the change's).

    python tests/lowered_hashes.py <tree> [program ...]

``<tree>``: the root of a checkout (its ``ray_tpu`` and its
``tests/test_chip_compile.py`` are the ones imported; the script may be run
from any other).  ``program``: names of ``PROGRAMS`` (none: all of them).
Lowered for the described ``v5e:2x2`` as the tests lower them (no chip; run it
with ``JAX_PLATFORMS=cpu``), ``.lower().as_text()`` with no debug locations
and no traceback in what locations a kernel's body carries.  Still, unpack
both trees AT ONE PATH, one after the other, so that nothing a program embeds
of a file's name can differ.  Prints a line a program (the first 16 hex digits
of each of its lowerings' sha256, in ``PROGRAMS``' order for it) and, last,
``HASHES`` and the same as JSON.  Not a test: pytest does not collect it.
"""

import hashlib
import json
import os
import sys


def main(argv):
    if not argv:
        raise SystemExit(__doc__)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp
    tree = os.path.abspath(argv[0])
    sys.path[:0] = [tree, os.path.join(tree, "tests")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_traceback_in_locations_limit", 0)
    import ray_tpu
    import test_chip_compile as programs

    for module in (ray_tpu, programs):
        assert os.path.abspath(module.__file__).startswith(tree + os.sep), (
            module.__file__, "is not under", tree)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    programs._FOUR_CHIPS[:] = topo.devices
    chip = SingleDeviceSharding(topo.devices[0])
    hashes = {}
    for name in argv[1:] or list(programs.PROGRAMS):
        lowered = programs.PROGRAMS[name](chip)
        hashes[name] = [
            hashlib.sha256(one.as_text().encode()).hexdigest()[:16]
            for one in (lowered if isinstance(lowered, (list, tuple)) else [lowered])]
        print(name, *hashes[name], flush=True)
    print("HASHES", json.dumps(hashes))


if __name__ == "__main__":
    main(sys.argv[1:])
