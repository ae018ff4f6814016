"""Launch-layer tests: sharding rules (divisibility fallback, axis
dedupe), batch/state specs, cell assembly — all on AbstractMesh (no
devices needed)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.data.pipeline import make_batch_specs
from repro.launch.sharding import batch_specs, param_specs, state_specs
from repro.launch.steps import cell_config, skip_reason
from repro.models import init_params, make_decode_state

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _leaf_specs(cfg, mesh=MESH):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(shapes, mesh)
    flat_sh, _ = jax.tree_util.tree_flatten_with_path(shapes)
    flat_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    return {
        jax.tree_util.keystr(kp): (leaf.shape, sp)
        for (kp, leaf), sp in zip(flat_sh, flat_sp)
    }


def _check_divisibility(leaves, mesh):
    for path, (shape, spec) in leaves.items():
        assert len(spec) <= len(shape), (path, shape, spec)
        used = []
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            n = 1
            for a in axes:
                assert a not in used, f"axis reused in {path}: {spec}"
                used.append(a)
                n *= mesh.shape[a]
            assert shape[d] % n == 0, f"{path}: {shape}[{d}] not divisible by {n} ({spec})"


@pytest.mark.parametrize("arch", ["yi-34b", "grok-1-314b", "deepseek-v2-lite-16b",
                                  "zamba2-2.7b", "rwkv6-3b", "whisper-small"])
def test_param_specs_divisibility(arch):
    cfg = get_config(arch)
    leaves = _leaf_specs(cfg)
    _check_divisibility(leaves, MESH)


def test_param_specs_multipod():
    cfg = get_config("granite-3-8b")
    leaves = _leaf_specs(cfg, MESH3)
    _check_divisibility(leaves, MESH3)


def test_embed_vocab_parallel_when_divisible():
    # yi vocab 64000 divides 16 → V over model, D unsharded (Megatron);
    # granite 49155 does not → fully replicated (divisibility fallback)
    yi = _leaf_specs(get_config("yi-34b"))
    embed = [v for k, v in yi.items() if k.endswith("['embed']")][0]
    assert embed[1] == P("model", None)
    gr = _leaf_specs(get_config("granite-3-8b"))
    embed = [v for k, v in gr.items() if k.endswith("['embed']")][0]
    assert embed[1] == P(None, None)


def test_yi_heads_fallback():
    """yi-34b: 56 heads don't divide 16 — wq's head-dim axis must fall
    back where needed but wq [D, H*hd]: 56*128=7168 divides 16 fine;
    the router-level check is that NOTHING asserts on divisibility."""
    cfg = get_config("yi-34b")
    leaves = _leaf_specs(cfg)
    wq = [v for k, v in leaves.items() if "wq" in k][0]
    assert wq[1][-1] == "model"  # 7168 % 16 == 0 → sharded (trailing dim)


def test_grok_experts_tp_fallback():
    """grok: 8 experts < 16-way model axis → EP falls back to TP inside
    the expert matrices."""
    cfg = get_config("grok-1-314b")
    leaves = _leaf_specs(cfg)
    w_in = [v for k, v in leaves.items() if "moe']['w_in" in k][0]
    shape, spec = w_in
    assert shape[-3] == 8
    assert spec[-3] is None  # experts NOT sharded (8 % 16 != 0)
    assert spec[-1] == "model"  # TP on the expert hidden dim


def test_deepseek_experts_ep():
    cfg = get_config("deepseek-v2-lite-16b")
    leaves = _leaf_specs(cfg)
    w_in = [v for k, v in leaves.items() if "moe']['w_in" in k][0]
    shape, spec = w_in
    assert shape[-3] == 64
    assert spec[-3] == "model"  # 64 experts over 16-way model = EP


def test_batch_specs_dp_and_sp():
    cfg = get_config("granite-3-8b")
    b = make_batch_specs(cfg, SHAPES["train_4k"])
    spec = batch_specs(b, MESH)
    # older jax does not normalize P(("data",), ...) == P("data", ...)
    assert spec["tokens"] in (P(("data",), None), P("data", None))
    # long-context (batch=1): sequence sharded instead
    b1 = {"tokens": jax.ShapeDtypeStruct((1, 524288), jnp.int32)}
    spec1 = batch_specs(b1, MESH, seq_sharded=True)
    assert spec1["tokens"] == P(None, "data")


def test_state_specs_batch_or_cache_sharded():
    cfg = cell_config("h2o-danube-3-4b", "decode_32k")
    st = jax.eval_shape(lambda: make_decode_state(cfg, 128, 32768))
    specs = state_specs(st, MESH)
    flat_st, _ = jax.tree_util.tree_flatten_with_path(st)
    flat_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    n_sharded = sum(
        1 for sp in flat_sp if any(ax is not None for ax in sp)
    )
    assert n_sharded >= len(flat_sp) // 2  # most state is sharded
    _check_divisibility(
        {jax.tree_util.keystr(kp): (l.shape, sp)
         for (kp, l), sp in zip(flat_st, flat_sp)},
        MESH,
    )


def test_skip_reasons():
    assert skip_reason("yi-34b", "long_500k") is not None
    assert skip_reason("rwkv6-3b", "long_500k") is None
    assert skip_reason("zamba2-2.7b", "long_500k") is None
    assert skip_reason("h2o-danube-3-4b", "long_500k") is None
    assert skip_reason("yi-34b", "train_4k") is None


def test_cell_config_overrides():
    cfg = cell_config("zamba2-2.7b", "long_500k")
    assert cfg.swa_window == 4096  # hybrid long-context window
    cfg2 = cell_config("yi-34b", "decode_32k")
    assert cfg2.remat is False and cfg2.microbatches == 1
    cfg3 = cell_config("yi-34b", "train_4k")
    assert cfg3.remat is True and cfg3.microbatches > 1
