"""Sharding rules: structural consistency for every assigned architecture."""
import numpy as np
import pytest
import jax
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import steps as S
from repro.sharding import rules


def _fake_mesh(shape=(16, 16), axes=("data", "model")):
    """An abstract mesh for spec construction only (no devices needed)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(shape, axes)


@pytest.mark.parametrize("arch", sorted(configs.ARCHS))
def test_param_specs_rank_and_divisibility(arch):
    cfg = configs.get(arch)
    mesh = _fake_mesh()
    params = S.abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        for spec_fn in (rules.param_spec, rules.param_spec_serve):
            spec = spec_fn(mesh, path, leaf)
            assert len(spec) == len(leaf.shape), (path, spec, leaf.shape)
            for dim, part in zip(leaf.shape, spec):
                if part is None:
                    continue
                size = int(np.prod([mesh.shape[a] for a in
                                    ((part,) if isinstance(part, str)
                                     else part)]))
                assert dim % size == 0, (path, spec, leaf.shape)


@pytest.mark.parametrize("arch", ["yi-6b", "zamba2-7b", "deepseek-v2-lite-16b"])
def test_cache_specs(arch):
    cfg = configs.get(arch)
    mesh = _fake_mesh()
    caches = S.abstract_caches(cfg, batch=128, max_seq=32768)
    flat = jax.tree_util.tree_flatten_with_path(caches)[0]
    for path, leaf in flat:
        spec = rules.cache_spec(mesh, path, leaf)
        assert len(spec) == len(leaf.shape)
        for dim, part in zip(leaf.shape, spec):
            if part is None:
                continue
            size = int(np.prod([mesh.shape[a] for a in
                                ((part,) if isinstance(part, str) else part)]))
            assert dim % size == 0, (path, spec, leaf.shape)


def test_serve_spec_strips_fsdp_only():
    cfg = configs.get("yi-6b")
    mesh = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
    params = S.abstract_params(cfg)
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in flat:
        train = rules.param_spec(mesh, path, leaf)
        serve = rules.param_spec_serve(mesh, path, leaf)
        for t_part, s_part in zip(train, serve):
            t_axes = set() if t_part is None else \
                set((t_part,) if isinstance(t_part, str) else t_part)
            s_axes = set() if s_part is None else \
                set((s_part,) if isinstance(s_part, str) else s_part)
            assert s_axes == t_axes - {"pod", "data"}


def test_batch_spec_fallbacks():
    mesh = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
    assert rules.batch_spec(mesh, 256) == P(("pod", "data"))
    assert rules.batch_spec(mesh, 48) == P("data")    # 48 % 32 != 0, % 16 == 0
    assert rules.batch_spec(mesh, 1) == P(None)       # long_500k decode
