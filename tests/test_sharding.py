"""Sharding rules: divisibility guards, spec/param structure agreement
(uses AbstractMesh — no devices touched)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import ARCHS
from repro.sharding import rules
from repro.train import step as step_mod

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("mesh", [MESH, MESH_MP], ids=["1pod", "2pod"])
def test_param_specs_divisible(arch, mesh):
    cfg = ARCHS[arch]
    shapes = step_mod.abstract_params(cfg)
    rules.FALLBACKS.clear()
    specs = rules.param_specs(cfg, mesh, shapes)

    def check(leaf, spec):
        assert len(spec) <= len(leaf.shape)
        for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * 8):
            if axes is None:
                continue
            axes = axes if isinstance(axes, tuple) else (axes,)
            k = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % k == 0, (arch, leaf.shape, spec)

    jax.tree.map(check, shapes, specs,
                 is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def test_attention_tp_sharding_when_heads_divide():
    cfg = ARCHS["command-r-35b"]  # 64 q heads, 16-way TP
    shapes = step_mod.abstract_params(cfg)
    specs = rules.param_specs(cfg, MESH, shapes)
    wq = specs["layers"]["attn"]["wq"]
    assert wq[-1] == "model"
    wo = specs["layers"]["attn"]["wo"]
    assert wo[-2] == "model"


def test_gqa_kv_replicated_when_small():
    cfg = ARCHS["yi-9b"]  # kv=4 < 16
    shapes = step_mod.abstract_params(cfg)
    rules.FALLBACKS.clear()
    specs = rules.param_specs(cfg, MESH, shapes)
    wk = specs["layers"]["attn"]["wk"]
    assert wk[-1] is None
    assert any("kv heads" in f for f in rules.FALLBACKS)


def test_vocab_sharded_on_model():
    cfg = ARCHS["qwen3-1.7b"]  # padded vocab 152064 % 16 == 0
    shapes = step_mod.abstract_params(cfg)
    specs = rules.param_specs(cfg, MESH, shapes)
    assert specs["embed"]["table"][0] == "model"


def test_batch_specs_fsdp():
    cfg = ARCHS["yi-9b"]
    b = step_mod.input_specs("yi-9b", "train_4k")
    specs = rules.batch_specs(cfg, MESH_MP, b)
    assert specs["tokens"][0] == ("pod", "data")
    # batch=1 decode replicates
    b1 = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32)}
    specs1 = rules.batch_specs(cfg, MESH_MP, b1)
    assert specs1["tokens"][0] is None


def test_padding_bookkeeping():
    q14 = ARCHS["qwen3-14b"]
    assert q14.n_heads == 48 and q14.logical_n_heads == 40
    assert q14.n_heads % 16 == 0
    wb = ARCHS["whisper-base"]
    assert wb.vocab % 128 == 0 and wb.logical_vocab == 51865
