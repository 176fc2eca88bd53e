"""Where a process that is about to use JAX keeps its compiled programs.

Every chip-holding worker is its own process, and several of them compile
the same programs (the GPT-2 train step, the serve engine's prefill and
decode): without a persistent cache each one compiles them cold.  JAX
reads its cache settings from the environment when it is imported, so
``configure()`` only edits the environment — it never imports jax, and it
must run before the process does.  The compile counter (``listen()`` /
``counts()``) needs jax's monitoring hooks, so it starts where a process
imports jax anyway (the serve engine's constructor), or in ``configure()``
if jax is already there.

Placement belongs to whoever runs the program: where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used and nothing
here sets another.  Otherwise the cache lives at one fixed, git-ignored
path in the checkout — never a temp name, pid or timestamp, because a
cache that moves is never hit.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict, Optional

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
    if "jax" in sys.modules:
        listen()  # never imports jax itself: see the module's docstring
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_DIR
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return path


# --- what this process compiled -------------------------------------------
# JAX reports one duration for every executable it builds OR loads from the
# persistent cache, and one more for each load; a program that compiles
# inside a measured window shows as a difference of two counts() calls
_BUILT_OR_LOADED = "/jax/core/compile/backend_compile_duration"
_LOADED = "/jax/compilation_cache/cache_retrieval_time_sec"
_lock = threading.Lock()
_listening = False
_counts = {"count": 0, "seconds": 0.0, "cache_hits": 0}


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _BUILT_OR_LOADED:
        with _lock:
            _counts["count"] += 1
            _counts["seconds"] += duration_secs
    elif event == _LOADED:
        with _lock:
            _counts["cache_hits"] += 1


def listen() -> None:
    """Start counting this process's compiles (once; imports jax, so a
    process calls it where it has decided to use JAX)."""
    global _listening
    import jax.monitoring

    with _lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def counts() -> Dict[str, float]:
    """Executables this process built or loaded from the persistent cache
    since :func:`listen`: how many, the seconds they took, and how many of
    them the cache answered (``cache_misses``: built here)."""
    with _lock:
        out = dict(_counts)
    out["cache_misses"] = out["count"] - out["cache_hits"]
    return out
