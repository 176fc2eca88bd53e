"""Where a process that is about to use JAX keeps its compiled programs.

Every chip-holding worker is its own process, and several of them compile
the same programs (the GPT-2 train step, the serve engine's prefill and
decode): without a persistent cache each one compiles them cold.  JAX
reads its cache settings from the environment when it is imported, so this
helper only edits the environment — it never imports jax, and it must run
before the process does.

Placement belongs to whoever runs the program: where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used and nothing
here sets another.  Otherwise the cache lives at one fixed, git-ignored
path in the checkout — never a temp name, pid or timestamp, because a
cache that moves is never hit.
"""

from __future__ import annotations

import os
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def configure() -> Optional[str]:
    """Point this process's JAX at the persistent compile cache; returns
    the directory in use.  Small programs are kept too (JAX's default
    skips compiles under a second): a worker start is dozens of them.

    A process held to the CPU gets no cache (returns None): XLA:CPU's
    cached results are tied to the CPU features of the machine that built
    them and reload with pages of warnings, and nothing there is worth
    minutes of compile."""
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return path
