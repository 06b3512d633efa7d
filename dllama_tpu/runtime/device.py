"""What this process runs on: where its compiled programs are kept, and
which devices JAX gave it.

One machine, one chip, one process: nothing here starts a child or looks
at the chip before the process that will use it. Callers that must not
touch JAX (``chip_smoke.py``, the fleet supervisor) learn the device from
the serving process's ``/stats``, which reports :func:`device_info`.
"""

from __future__ import annotations

import os

import jax

#: the checkout's root (this file is dllama_tpu/runtime/device.py)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_EV_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_EV_HITS = "/jax/compilation_cache/cache_hits"
#: process-wide, like the cache they describe; None until configured
_counts: dict | None = None


def _count_event(event: str, **_kw) -> None:
    if event in _counts:
        _counts[event] += 1


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` decides where, and JAX reads it itself:
    when it is set this function sets no path. Unset, the cache goes to
    ``<checkout>/.jax_cache`` — one FIXED path (the directory is part of
    every entry's key, so a path built from a pid, a temp name or the time
    would never hit). Call once per process, before the first compile.
    Compile requests and cache hits are counted from here on
    (:func:`compile_cache_counts`)."""
    global _counts
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if _counts is None:  # JAX keeps listeners for the life of the process
        _counts = {_EV_REQUESTS: 0, _EV_HITS: 0}
        jax.monitoring.register_event_listener(_count_event)
    return path


def compile_cache_counts() -> dict:
    """Programs this process asked the compiler for since
    :func:`configure_compile_cache`, and how many of them the persistent
    cache answered instead."""
    counts = _counts or {}
    return {
        "dir": jax.config.jax_compilation_cache_dir,
        "requests": counts.get(_EV_REQUESTS, 0),
        "hits": counts.get(_EV_HITS, 0),
    }


def device_info() -> dict:
    """The devices as JAX reports them. ``bytes_in_use`` has one entry per
    device: under ``--tp N`` it is how to see that every device holds its
    share of the weights. None where the backend keeps no allocator
    statistics (the CPU) and for another host's device: under
    ``jax.distributed`` ``jax.devices()`` is the whole job's list, and
    ``memory_stats()`` raises for a device this process cannot address."""
    devs = jax.devices()
    me = jax.process_index()
    in_use = []
    for d in devs:
        stats = d.memory_stats() if d.process_index == me else None
        in_use.append(stats.get("bytes_in_use") if stats else None)
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "bytes_in_use": in_use,
    }
