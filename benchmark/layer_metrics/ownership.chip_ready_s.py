"""Device ownership: from the start of ``ray_tpu.init()`` in the parent to
the chip-holding process's first ``jax.devices()`` returning (cluster up,
worker spawned with the chip's environment, backend started)."""

UNIT = "s"


def read(ctx, raw):
    holder = raw if raw["kind"] == "train" else raw.get("replica")
    if not holder or "t_chip" not in holder:
        return None
    return holder["t_chip"] - raw["t_init"]
