"""What this process can observe about the backend it runs on.

Three answers every entry point and kernel dispatch shares, so none of
them guesses on its own:

- :func:`on_tpu` — the one platform predicate of the package.  Pallas
  kernels compile through Mosaic when it is true and run in interpret
  mode (CPU tests) when it is false; it raises whatever
  ``jax.devices()`` raises, so a broken backend is an error and never a
  silent interpret-mode run.
- :func:`device_summary` — platform, device kind and device count as JAX
  reports them; ``train.py`` and ``serve.py`` print it at start-up and
  ``chip_smoke.py`` refuses anything whose platform is not ``tpu``.
- :func:`init_compile_cache` — where the persistent XLA compilation cache
  lives.  The cache key includes the directory, so it must be the same
  path on every run.
"""

from __future__ import annotations

import os

import jax

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout (gitignored), derived from this file so
#: it does not depend on the working directory.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def on_tpu() -> bool:
    """True when the default backend's devices are TPU chips."""
    return jax.devices()[0].platform == "tpu"


def use_kernel(impl: str) -> bool:
    """Whether an op asked for ``impl`` ("auto", "pallas" or "xla") takes
    its Pallas kernel: always for "pallas", on a TPU for "auto"."""
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"impl must be auto, pallas or xla, got {impl!r}")
    return impl == "pallas" or (impl == "auto" and on_tpu())


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_tpu() -> dict:
    """Exit unless the default backend is a TPU; returns the
    :func:`device_summary` so callers log what they checked."""
    summary = device_summary()
    if summary["platform"] != "tpu":
        raise SystemExit(
            "this run requires a tpu device; JAX found "
            f"platform={summary['platform']!r} kind={summary['kind']!r} "
            f"count={summary['count']}"
        )
    return summary


def init_compile_cache() -> str:
    """Place the persistent compilation cache; call before the first jit.

    With ``JAX_COMPILATION_CACHE_DIR`` set this does nothing — JAX reads
    the variable itself and no other directory is set in code.  Without
    it the cache goes to :data:`COMPILE_CACHE_DIR`.  Returns the
    directory in use.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
