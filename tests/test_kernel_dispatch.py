"""kernels/ops.py dispatch logic: _decide/_platform and the backend mapping.

The tri-state `use_pallas` flag and the EngineConfig `backend` strings are
the only switchboard between the pure-jnp reference paths and the Pallas
kernels (DESIGN.md §11); these tests pin the decision table down explicitly,
including the CPU force-pallas -> interpret route.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import ops, ref


# --- the decision table ----------------------------------------------------

@pytest.mark.parametrize(
    "on_tpu,use_pallas,want",
    [
        # (run_pallas, interpret)
        (True, None, (True, False)),     # auto on TPU -> native Pallas
        (False, None, (False, False)),   # auto on CPU -> reference
        (True, True, (True, False)),     # forced on TPU -> native Pallas
        (False, True, (True, True)),     # forced on CPU -> interpret mode
        (True, False, (False, False)),   # off -> reference, everywhere
        (False, False, (False, False)),
    ])
def test_decide_table(monkeypatch, on_tpu, use_pallas, want):
    monkeypatch.setattr(ops, "_platform", lambda: "tpu" if on_tpu else "cpu")
    assert ops._decide(use_pallas) == want


@pytest.mark.parametrize("use_pallas", [None, True])
def test_decide_rejects_other_backends(monkeypatch, use_pallas):
    """No silent fallback: a backend with neither a native lowering nor the
    interpreter fails instead of quietly running the reference."""
    monkeypatch.setattr(ops, "_platform", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._decide(use_pallas)
    assert ops._decide(False) == (False, False)


def test_on_tpu_matches_default_backend(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._platform() == "tpu"
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._platform() == "cpu"


# --- EngineConfig.backend -> use_pallas mapping ----------------------------

@pytest.mark.parametrize("backend,want",
                         [("reference", False), ("pallas", True),
                          ("auto", None)])
def test_use_pallas_flag(backend, want):
    assert ops.use_pallas_flag(backend) is want


def test_use_pallas_flag_rejects_unknown():
    with pytest.raises(ValueError, match="backend"):
        ops.use_pallas_flag("cuda")


def test_engine_config_validates_backend():
    from repro.core.engine import EngineConfig
    with pytest.raises(ValueError, match="backend"):
        EngineConfig(backend="cuda")
    for backend in ops.BACKENDS:
        assert EngineConfig(backend=backend).backend == backend


# --- the wrappers actually route where the table says ----------------------

def test_force_pallas_off_tpu_takes_interpret_route(monkeypatch):
    """On this CPU container use_pallas=True must reach the Pallas kernel
    with interpret=True (not the reference, not a native lowering)."""
    calls = {}
    real = ops._gk.gaussian_nbody

    def spy(*args, **kwargs):
        calls["interpret"] = kwargs.get("interpret")
        return real(*args, **kwargs)

    monkeypatch.setattr(ops._gk, "gaussian_nbody", spy)
    rng = np.random.default_rng(3)
    t = jnp.array(rng.uniform(0, 100, (5, 3)), jnp.float32)
    s = jnp.array(rng.uniform(0, 100, (6, 3)), jnp.float32)
    w = jnp.ones((6,), jnp.float32)
    got = ops.gaussian_nbody(t, s, w, 750.0 ** 2, use_pallas=True)
    assert calls["interpret"] is True
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.gaussian_nbody(t, s, w,
                                                             750.0 ** 2)),
                               rtol=2e-5)


def test_force_reference_never_touches_pallas(monkeypatch):
    def boom(*args, **kwargs):
        raise AssertionError("Pallas kernel called with use_pallas=False")

    monkeypatch.setattr(ops._gk, "gaussian_nbody", boom)
    monkeypatch.setattr(ops._m2l, "m2l_separable", boom)
    monkeypatch.setattr(ops._msp, "msp_update", boom)
    rng = np.random.default_rng(4)
    t = jnp.array(rng.uniform(0, 100, (4, 3)), jnp.float32)
    w = jnp.ones((4,), jnp.float32)
    ops.gaussian_nbody(t, t, w, 750.0 ** 2, use_pallas=False)
    moms = jnp.array(rng.uniform(0, 1, (4, 64)), jnp.float32)
    herm = jnp.array(rng.uniform(-1, 1, (4, 64)), jnp.float32)
    y = jnp.array(rng.uniform(-1, 1, (4, 3)), jnp.float32)
    ops.m2l_separable(moms, herm, y, use_pallas=False)
    from repro.core.msp import MSPConfig
    n = 8
    ops.msp_update(jnp.zeros(n), jnp.zeros(n, jnp.int32), jnp.zeros(n),
                   jnp.zeros(n), jnp.zeros(n), MSPConfig(), use_pallas=False)
