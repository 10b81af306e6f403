"""Partition-rule unit tests (AbstractMesh — no multi-device env needed)."""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.distributed import sharding as shd
from repro.launch import steps as S
from repro.launch.mesh import make_mesh


def _mesh(data=2, model=2):
    return AbstractMesh((data, model), ("data", "model"))


def test_attention_and_mlp_rules():
    mesh = _mesh()
    cfg = get_smoke_config("qwen3_1_7b")
    abs_p = S.abstract_params(cfg)
    specs = shd.param_sharding_rules(abs_p, mesh, fsdp=False)
    blocks = specs["blocks"]
    assert blocks["attn"]["wq"] == P(None, None, "model")
    assert blocks["attn"]["wo"] == P(None, "model", None)
    assert blocks["mlp"]["w1"] == P(None, None, "model")
    assert blocks["mlp"]["w2"] == P(None, "model", None)
    assert specs["embed"] == P("model", None)
    assert specs["lm_head"] == P(None, "model")
    assert specs["ln_f"] in (P(), P(None))


def test_moe_expert_parallel_rules():
    mesh = _mesh()
    cfg = get_smoke_config("granite_moe_3b_a800m")
    specs = shd.param_sharding_rules(S.abstract_params(cfg), mesh, fsdp=False)
    assert specs["blocks"]["moe"]["w1"] == P(None, "model", None, None)
    assert specs["blocks"]["moe"]["router"] == P(None, None, None)


def test_fsdp_adds_data_axis_on_large_leaves():
    mesh = _mesh()
    big = jax.eval_shape(lambda: {"blocks": {"mlp": {
        "w1": jnp.zeros((16, 4096, 4096), jnp.bfloat16)}}})
    spec = shd.param_sharding_rules(big, mesh, fsdp=True)
    assert spec["blocks"]["mlp"]["w1"] == P(None, "data", "model")
    # small leaves stay unsharded on data
    small = jax.eval_shape(lambda: {"blocks": {"mlp": {
        "w1": jnp.zeros((2, 64, 64), jnp.bfloat16)}}})
    spec = shd.param_sharding_rules(small, mesh, fsdp=True)
    assert "data" not in tuple(spec["blocks"]["mlp"]["w1"])


def test_constrain_noop_outside_scope():
    x = jnp.zeros((4, 8))
    assert shd.constrain(x, ("batch", None)) is x
    assert shd.data_shards() == 1


def test_constrain_inside_scope_single_device():
    mesh = make_mesh((1, 1), ("data", "model"))
    with shd.activation_sharding(mesh):
        x = jnp.zeros((3, 5))
        y = shd.constrain(x, ("batch", "model"))
        assert y.shape == x.shape
        assert shd.data_shards() == 1


def test_cache_sharding_rules():
    mesh = _mesh()
    cfg = get_smoke_config("qwen3_1_7b")
    cache_abs = S.abstract_cache(cfg, batch=4, max_seq=128)
    specs = shd.cache_sharding_rules(cache_abs, mesh)
    k_spec = specs["kv"].k
    # batch 4 % 2 == 0; the composite-axis entry ("data",) is spec-equivalent
    assert k_spec[1] in ("data", ("data",))
    assert k_spec[3] in ("model", None)


def test_batch_sharding_composite_axis():
    multi = AbstractMesh((2, 4, 4), ("pod", "data", "model"))
    assert shd.batch_sharding(multi, 2) == P(("pod", "data"), None)


# ---------------------------------------------------------------------------
# divisibility / rank-fitting edge cases (the machinery every rule runs on)
# ---------------------------------------------------------------------------

def test_fit_rank_pads_and_truncates():
    # shorter spec than leaf rank: scan (layer-stack) axis gets None
    assert shd._fit_rank(P(None, "model"), 3) == [None, None, "model"]
    # longer spec than leaf rank: keep the TRAILING entries (the rule's
    # meaningful dims are rightmost)
    assert shd._fit_rank(P("model", None), 1) == [None]
    assert shd._fit_rank(P("model", None), 0) == []


def test_divisible_odd_head_counts():
    mesh = _mesh(data=2, model=2)
    # 7 heads on a 2-wide axis: not divisible
    assert not shd._divisible(["model", None], (7, 64), mesh)
    assert shd._divisible(["model", None], (8, 64), mesh)
    # composite-axis entry multiplies sizes: 4 needed
    assert not shd._divisible([("data", "model")], (6,), mesh)
    assert shd._divisible([("data", "model")], (8,), mesh)


def test_divisible_one_sized_mesh_axes():
    mesh = _mesh(data=1, model=1)
    # size-1 axes divide everything — odd dims included
    assert shd._divisible(["model", "data"], (7, 13), mesh)
    cfg = get_smoke_config("qwen3_1_7b")
    specs = shd.param_sharding_rules(S.abstract_params(cfg), mesh,
                                    fsdp=False)
    # rules still produce model-axis entries (sharding into 1 piece is
    # valid and keeps the spec stable across mesh sizes)
    assert specs["blocks"]["attn"]["wq"] == P(None, None, "model")


def test_spec_for_strips_non_dividing_axes():
    mesh = _mesh(data=2, model=16)
    # odd rows AND cols on a 16-wide model axis: every candidate fails,
    # the last-resort path strips the non-dividing entries instead of
    # crashing (granite's 40-expert case generalized)
    assert shd._spec_for("blocks/attn/wq", (24, 24), mesh) == P(None, None)
    # only the free dim fails -> the contract-dim candidate is used
    assert shd._spec_for("blocks/attn/wq", (64, 24), mesh) == \
        P("model", None)


def test_cache_rules_unmatched_leaves_fall_through():
    """Cache trees with leaves matching NO rule (not 5-dim KV, not 4-dim
    latent, not 'memory') must come back fully replicated, not crash."""
    mesh = _mesh()
    weird = jax.eval_shape(lambda: {
        "scalar_state": jnp.zeros((), jnp.float32),          # 0-dim
        "conv_state": jnp.zeros((2, 4, 3), jnp.float32),     # 3-dim, odd
        "flags": jnp.zeros((2, 7), jnp.int32),               # 2-dim, odd
    })
    specs = shd.cache_sharding_rules(weird, mesh)
    assert specs["scalar_state"] == P()
    assert specs["conv_state"] == P(None, None, None)
    assert specs["flags"] == P(None, None)


def test_flash_cache_rules_non_dividing_heads_fall_back():
    """attn_kernel='flash' head sharding only engages when kv_heads
    divides the model axis; otherwise the sequence-sharded chunked layout
    is kept (the flash resolver raises before this layout is used)."""
    mesh = _mesh(data=2, model=4)
    cfg = get_smoke_config("qwen3_1_7b")          # n_kv_heads = 2
    cache_abs = S.abstract_cache(cfg, batch=4, max_seq=128)
    specs = shd.cache_sharding_rules(cache_abs, mesh, attn_kernel="flash")
    k_spec = specs["kv"].k
    assert k_spec[3] is None and k_spec[2] == "model"
