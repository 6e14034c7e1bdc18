"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy: on TPU the Pallas kernels run natively.  On CPU (tests,
CI) they run through the interpreter only when explicitly requested,
otherwise the pure-jnp reference executes — interpret mode runs the kernel
body in Python per grid step, which is correct but slow, so it is reserved
for validation.  Any other backend has neither path and is an error.

    use_pallas=None   -> auto: Pallas on TPU, reference on CPU
    use_pallas=True   -> force Pallas (interpret=True on CPU)
    use_pallas=False  -> force reference, on every backend
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import gaussian_nbody as _gk
from repro.kernels import m2l_pair as _m2l
from repro.kernels import msp_update as _msp
from repro.kernels import ref as _ref


# Engine-facing backend names (EngineConfig.backend, DESIGN.md §11).
BACKENDS = ("reference", "pallas", "auto")


def use_pallas_flag(backend: str) -> Optional[bool]:
    """Map an EngineConfig.backend string onto the `use_pallas` tri-state."""
    if backend not in BACKENDS:
        raise ValueError(
            f"backend must be one of {BACKENDS}, got {backend!r}")
    return {"reference": False, "pallas": True, "auto": None}[backend]


def _platform() -> str:
    return jax.default_backend()


def _decide(use_pallas: Optional[bool]):
    """-> (run_pallas, interpret)"""
    if use_pallas is False:
        return (False, False)
    platform = _platform()
    if platform == "tpu":
        return (True, False)
    if platform == "cpu":
        return (bool(use_pallas), bool(use_pallas))
    raise RuntimeError(
        f"Pallas kernels run natively on TPU and interpreted on CPU; "
        f"backend {platform!r} has neither (use_pallas=False selects the "
        f"reference)")


def gaussian_nbody(targets, sources, weights, delta,
                   use_pallas: Optional[bool] = None):
    run, interp = _decide(use_pallas)
    if run:
        return _gk.gaussian_nbody(targets, sources, weights, delta,
                                  interpret=interp)
    return _ref.gaussian_nbody(targets, sources, weights, delta)


def msp_update(x, refrac, calcium, syn_input, uniform, cfg,
               use_pallas: Optional[bool] = None):
    """cfg: repro.core.msp.MSPConfig."""
    kw = dict(x0=cfg.x0, tau_x=cfg.tau_x, background=cfg.background,
              w_syn=cfg.w_syn, beta_ca=cfg.beta_ca, tau_ca=cfg.tau_ca,
              refractory=cfg.refractory)
    run, interp = _decide(use_pallas)
    if run:
        x2, r2, s2, c2 = _msp.msp_update(x, refrac, calcium, syn_input,
                                         uniform, interpret=interp, **kw)
        return x2, r2, s2 > 0.5, c2
    x2, r2, s2, c2 = _ref.msp_update(x, refrac, calcium, syn_input, uniform,
                                     **kw)
    return x2, r2, s2, c2


def m2l_separable(moms, herm, y, p: int = 4,
                  use_pallas: Optional[bool] = None):
    run, interp = _decide(use_pallas)
    if run:
        return _m2l.m2l_separable(moms, herm, y, p=p, interpret=interp)
    return _ref.m2l_separable(moms, herm, y, p=p)
