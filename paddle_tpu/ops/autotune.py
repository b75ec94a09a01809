"""Kernel autotune: runtime implementation selection + persistent cache.

Capability parity with the reference's kernel autotune
(reference: paddle/phi/kernels/autotune/ — cache.cc keyed per op+shape,
auto_tune_base.h timing candidate kernels, switch_autotune.cc).

TPU-native: candidates are whole implementations (Pallas kernel vs XLA
fusion) rather than cudnn algorithms.  On an *eager* call with concrete
arrays the candidates are timed once per shape key and the winner is cached
(in-memory + JSON on disk).  Under tracing (jit) timing is impossible, so a
cached winner is used when present, else the caller's analytical heuristic.
"""
from __future__ import annotations

import json
import os
import threading
import time
import warnings
from typing import Callable, Dict, List, Optional, Tuple

from ..framework.compile_cache import CACHE_ROOT

# winners live beside the compilation cache, inside the checkout: the
# program a traced call compiles depends on them, so it must not depend
# on a file in the user's home
_CACHE_PATH = os.environ.get(
    "PADDLE_TPU_AUTOTUNE_CACHE", os.path.join(CACHE_ROOT, "autotune.json"))

_lock = threading.Lock()
_cache: Optional[Dict[str, str]] = None
_enabled = True
_device_tag: Optional[str] = None
# what this process chose, and which candidates it could not run —
# read by chip_smoke.py to print the choices and to fail on a refusal
_decisions: Dict[str, Tuple[str, str]] = {}
_failures: List[Tuple[str, str, str]] = []


def _get_device_tag() -> str:
    """Winners are only valid for the device they were measured on."""
    global _device_tag
    if _device_tag is None:
        import jax
        d = jax.devices()[0]
        _device_tag = f"{d.platform}/{d.device_kind}"
    return _device_tag


def _full_key(key: str) -> str:
    return f"{_get_device_tag()}::{key}"


def _load() -> Dict[str, str]:
    global _cache
    if _cache is None:
        try:
            with open(_CACHE_PATH) as f:
                _cache = json.load(f)
        except (OSError, json.JSONDecodeError):
            _cache = {}
    return _cache


def _persist() -> None:
    try:
        os.makedirs(os.path.dirname(_CACHE_PATH), exist_ok=True)
        tmp = _CACHE_PATH + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(_cache, f, indent=1, sort_keys=True)
        os.replace(tmp, _CACHE_PATH)
    except OSError:
        pass


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


# switch through the framework flag registry (reference:
# paddle/phi/kernels/autotune/switch_autotune.cc + FLAGS_use_autotune);
# env FLAGS_use_autotune is ingested by define_flag, set_flags updates live
from ..framework.flags import define_flag, get_flag  # noqa: E402

define_flag("use_autotune", True,
            "measure and cache kernel-implementation choices",
            on_change=set_enabled)
_enabled = bool(get_flag("use_autotune"))


def lookup(key: str) -> Optional[str]:
    with _lock:
        return _load().get(_full_key(key))


def note(key: str, impl: str, source: str) -> str:
    """Record that ``key`` took ``impl`` (``source``: "cached",
    "measured", "default" or the caller's own word); returns ``impl``."""
    with _lock:
        _decisions[key] = (impl, source)
    return impl


def decisions() -> Dict[str, Tuple[str, str]]:
    """{key: (implementation, source)} for every choice this process
    made through :func:`select` / :func:`autotune` / :func:`note`."""
    with _lock:
        return dict(_decisions)


def failures() -> List[Tuple[str, str, str]]:
    """(key, candidate, error) for every candidate that failed to
    compile or run while being measured in this process."""
    with _lock:
        return list(_failures)


def record(key: str, winner: str) -> None:
    with _lock:
        _load()[_full_key(key)] = winner
        _persist()


def _time_one(fn: Callable, repeats: int = 3) -> float:
    import jax
    out = fn()                       # compile + warm
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def select(key: str, arr, candidates: Dict[str, Callable],
           default: str, tpu_only: bool = True) -> str:
    """Shared impl-selection policy (attention / rmsnorm / rope):
    under tracing use the cached winner (or default, never measure);
    eagerly on TPU measure-and-cache; elsewhere the default."""
    import jax
    if isinstance(arr, jax.core.Tracer):
        hit = lookup(key)
        return note(key, hit or default, "cached" if hit else "default")
    if tpu_only and jax.default_backend() != "tpu":
        return note(key, default, "default")
    return autotune(key, candidates, default)


def autotune(key: str, candidates: Dict[str, Callable],
             default: str) -> str:
    """Winner for ``key``: cached if known; measured now if enabled and all
    candidates are runnable; else ``default``."""
    if not _enabled:
        return note(key, default, "default")
    hit = lookup(key)
    if hit in candidates:
        return note(key, hit, "cached")
    timings = {}
    for name, fn in candidates.items():
        try:
            timings[name] = _time_one(fn)
        except Exception as e:  # noqa: BLE001 — any refusal is reported
            # the candidate loses, but never in silence: a kernel the
            # compiler refuses is a defect, not a slow kernel
            with _lock:
                _failures.append((key, name, repr(e)))
            warnings.warn(f"autotune {key}: candidate {name!r} failed "
                          f"to compile or run: {e!r}")
    if not timings:
        return note(key, default, "default")
    winner = min(timings, key=timings.get)
    record(key, winner)
    return note(key, winner, "measured")
