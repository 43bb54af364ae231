"""Process-wide runtime knobs shared by the train and serve hot paths.

Buffer donation is the one invariant that used to be gated by two
independent hard-coded backend checks (``train.backends.donate_argnums``
and ``serve.Engine._donate``), which made the donation story invisible to
any CPU-hosted introspection: a trace on the CI container always saw zero
donated invars, so coverage regressions on TPU could never be caught before
they shipped.  Both sites now route through here, and
``REPRO_ASSUME_DONATION=1`` makes the jit wrappers *request* donation
regardless of backend — callers that only trace (``jax.make_jaxpr`` /
``jax.eval_shape``, e.g. ``repro.analysis``) see the real donation masks
without ever compiling, so no CPU "donation unimplemented" warnings fire.

``enable_compile_cache`` is the one place the persistent XLA compile cache
is switched on; entry points (``main()`` of the launch CLIs and
``chip_smoke.py``) call it, nothing does at import.
"""
from __future__ import annotations

import contextlib
import os
from typing import Tuple

import jax

_ASSUME_ENV = "REPRO_ASSUME_DONATION"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def donation_assumed() -> bool:
    return os.environ.get(_ASSUME_ENV, "") == "1"


def donation_enabled() -> bool:
    """Whether jitted steps should request buffer donation: real backends
    that implement aliasing, or any backend under REPRO_ASSUME_DONATION=1
    (trace-only introspection)."""
    if donation_assumed():
        return True
    try:
        backend = jax.default_backend()
    except Exception:  # pragma: no cover
        return False
    return backend in ("gpu", "tpu")


def donate_argnums(*nums: int) -> Tuple[int, ...]:
    """The donate_argnums tuple to pass to jax.jit — ``nums`` where donation
    is enabled, ``()`` elsewhere (CPU would warn per call and ignore it)."""
    return tuple(nums) if donation_enabled() else ()


@contextlib.contextmanager
def assume_donation():
    """Force donation requests on for the duration (restores the prior env).

    Only safe around code that traces — executing a donate-jitted step on
    CPU under this context would emit XLA donation warnings."""
    prev = os.environ.get(_ASSUME_ENV)
    os.environ[_ASSUME_ENV] = "1"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(_ASSUME_ENV, None)
        else:
            os.environ[_ASSUME_ENV] = prev


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself and
    it is left alone); otherwise the cache lives at the fixed path
    ``<repo>/.jax_cache`` — the path is part of the cache key, so it must
    not move between runs."""
    path = os.environ.get(_CACHE_ENV)
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
