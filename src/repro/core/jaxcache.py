"""JAX's persistent compilation cache for the repo's entry points.

`enable()` is called by the entry points (`chip_smoke.py`,
`benchmarks/run.py`, `python -m repro.serve.cv_engine`), never when a
module is imported.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads
it itself and this sets no other directory; otherwise the cache lives at
a fixed ``<checkout>/.jax_cache`` (the path is part of what a later run
must find again).  `counts()` reports compile requests against cache
hits, so a run can show whether it compiled or reused; each request also
leaves an instant ``jaxcache.request`` span in a profiler capture, so a
trace counts them per ``serve.submit`` span.
"""
from __future__ import annotations

import os

from jax.profiler import TraceAnnotation

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_ROOT, ".jax_cache")

_COUNTS = {"requests": 0, "hits": 0}
_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
           "/jax/compilation_cache/cache_hits": "hits"}
_LISTENING = False


def _on_event(event: str, **_kw) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _COUNTS[key] += 1
    if key == "requests":
        with TraceAnnotation("jaxcache.request"):
            pass


def enable() -> str:
    """Turn the persistent cache on for every program this process
    compiles (no minimum compile time); returns the cache directory."""
    global _LISTENING
    import jax
    from jax import monitoring

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _LISTENING:
        monitoring.register_event_listener(_on_event)
        _LISTENING = True
    return path


def counts() -> dict:
    """``{"requests", "hits", "compiled"}`` since `enable()`: programs
    that asked the cache, found their entry, and had to compile."""
    return {**_COUNTS, "compiled": _COUNTS["requests"] - _COUNTS["hits"]}
