"""Zamba2 on the program's normal path against the plain float32 reference
(``benchmarks/chip/configs/zamba2-7b.py``, loaded by path) and against
transformers' ``Zamba2ForCausalLM``, on the CPU at a reduced size: d 64,
2 shared blocks, adapter rank 8, n_groups 2, the published first 13
layers (sites 6 and 11), seeded weights from the benchmark's
``weights.make_weights``, float32 throughout."""

import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SSMConfig, SharedBlockConfig, get_config, reduced
from repro.models import build_model

CHIP = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load(CHIP / "configs" / "zamba2-7b.py", "zamba2_reference")
weights = _load(CHIP / "weights.py", "bench_weights")

KINDS = get_config("zamba2-7b").layer_kinds()[:13]
#: the reference's sizes, under the published config's keys; the head size
#: is transformers' 2·hidden_size / num_attention_heads
SIZES = dict(
    hidden_size=64,
    intermediate_size=128,
    vocab_size=256,
    num_attention_heads=4,
    num_key_value_heads=4,
    attention_head_dim=32,
    rms_norm_eps=1e-5,
    rope_theta=10000.0,
    mamba_d_state=16,
    mamba_d_conv=4,
    mamba_expand=2,
    mamba_headdim=16,
    mamba_ngroups=2,
    time_step_min=0.001,
    num_mem_blocks=2,
    adapter_rank=8,
    layers_block_type=list(KINDS),
)
CFG = reduced(
    get_config("zamba2-7b"),
    n_layers=13,
    pattern=KINDS,
    n_kv_heads=4,
    head_dim=32,
    norm_eps=1e-5,
    ssm=SSMConfig(d_state=16, head_dim=16, expand=2, n_groups=2, chunk=16, dt_min=0.001),
    shared=SharedBlockConfig(n_blocks=2, adapter_rank=8),
)
SEED = 2**31 + 77
B, T = 2, 32  # T a multiple of the SSD chunk (16) for forward
#: float32 on both sides; what is left is the order of summation (the
#: program's chunked SSD and split projections against the reference's
#: stepped recurrence, transformers' chunked SSD) over 13 layers.  The
#: widest gaps measured are 1.4e-6 (program) and 1.5e-6 (transformers) on
#: logits up to 0.71; the least planted fault (e0 dropped) moves them by
#: 9.7e-3.
ATOL = 5e-5


@pytest.fixture(scope="module")
def api():
    return build_model(CFG)


@pytest.fixture(scope="module")
def params(api):
    return weights.make_weights(jax.eval_shape(api.init, jax.random.PRNGKey(0)), SEED)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, CFG.vocab_size, jnp.int32)


@pytest.fixture(scope="module")
def want(params, tokens):
    padded = jnp.pad(tokens, ((0, 0), (0, ref.BLOCK - T)))
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, SIZES, padded)[:, :T])


def _gap(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32) - want)))


def test_layers_and_sites():
    assert CFG.layer_runs() == ((0, 1, 6), (6, 1, 1), (7, 1, 4), (11, 1, 1), (12, 1, 1))
    assert [CFG.shared_block(i) for i in (6, 11)] == [0, 1]
    published = get_config("zamba2-7b")
    sites = [i for i, k in enumerate(published.layer_kinds()) if k == "hybrid"]
    assert sites == [6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77]
    assert [published.shared_block(i) for i in sites] == [0, 1] * 6 + [0]


def test_prefill_through_decode_step_matches_reference(api, params, tokens, want):
    step = jax.jit(api.decode_step)
    cache = api.init_cache(B, T)
    outs = []
    for i in range(T):
        lg, cache = step(params, cache, tokens[:, i : i + 1], jnp.int32(i))
        outs.append(lg[:, 0])
    assert _gap(jnp.stack(outs, axis=1), want) < ATOL


def test_forward_matches_reference(api, params, tokens, want):
    logits, _ = jax.jit(api.forward)(params, tokens)
    assert _gap(logits, want) < ATOL


def _swap_blocks(params, monkeypatch):
    shared = params["shared"]
    return {**params, "shared": {"block0": shared["block1"], "block1": shared["block0"]}}


def _drop_e0(params, monkeypatch):
    from repro.models import transformer

    real = transformer.shared_block
    monkeypatch.setattr(
        transformer, "shared_block",
        lambda sp, bp, x, e0, *a, **k: real(sp, bp, x, jnp.zeros_like(e0), *a, **k),
    )
    return params


def _drop_adapter(params, monkeypatch):
    groups = dict(params["groups"])
    for key in ("pos6", "pos11"):
        site = dict(groups[key])
        site["adapter"] = jax.tree.map(jnp.zeros_like, site["adapter"])
        groups[key] = site
    return {**params, "groups": groups}


def _residual_around_block(params, monkeypatch):
    """x ← x + T + Mamba(Norm(x + T)): the block's output kept in the
    residual stream."""
    from repro.models import transformer

    real_block, real_mixer = transformer.shared_block, transformer.mamba_block
    held = []

    def block(*a, **k):
        t, kv = real_block(*a, **k)
        held.append(t)
        return t, kv

    def mixer(*a, **k):
        out, state = real_mixer(*a, **k)
        return (out + held.pop() if held else out), state

    monkeypatch.setattr(transformer, "shared_block", block)
    monkeypatch.setattr(transformer, "mamba_block", mixer)
    return params


def _ungrouped_gate_norm(params, monkeypatch):
    from repro.models import layers, mamba2

    monkeypatch.setattr(
        mamba2, "rms_norm", lambda x, p, eps, groups=1: layers.rms_norm(x, p, eps)
    )
    return params


FAULTS = {
    "blocks_swapped": _swap_blocks,
    "e0_dropped": _drop_e0,
    "adapter_dropped": _drop_adapter,
    "residual_around_block": _residual_around_block,
    "ungrouped_gate_norm": _ungrouped_gate_norm,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_breaks_tolerance(fault, params, tokens, want, monkeypatch):
    broken = FAULTS[fault](params, monkeypatch)
    api = build_model(CFG)  # traced afresh, under the fault
    logits, _ = jax.jit(api.forward)(broken, tokens)
    assert _gap(logits, want) > 10 * ATOL


def test_reference_matches_transformers(params, tokens, want):
    """The reference is the published model: transformers' Zamba2 (its
    torch path, float32, eager attention) on the same weights."""
    pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    from transformers import Zamba2Config, Zamba2ForCausalLM

    s = SIZES
    hf_cfg = Zamba2Config(
        vocab_size=s["vocab_size"],
        hidden_size=s["hidden_size"],
        num_hidden_layers=len(KINDS),
        layers_block_type=list(KINDS),
        mamba_d_state=s["mamba_d_state"],
        mamba_d_conv=s["mamba_d_conv"],
        mamba_expand=s["mamba_expand"],
        mamba_ngroups=s["mamba_ngroups"],
        n_mamba_heads=s["mamba_expand"] * s["hidden_size"] // s["mamba_headdim"],
        time_step_min=s["time_step_min"],
        intermediate_size=s["intermediate_size"],
        hidden_act="gelu",
        num_attention_heads=s["num_attention_heads"],
        num_key_value_heads=s["num_key_value_heads"],
        num_mem_blocks=s["num_mem_blocks"],
        adapter_rank=s["adapter_rank"],
        use_shared_attention_adapter=False,
        use_mem_rope=True,
        rope_theta=s["rope_theta"],
        rms_norm_eps=s["rms_norm_eps"],
        # the published chunk: the sequence stays within one, since the
        # torch path's inter-chunk state pass sums over the wrong chunk axis
        # (``result = (decay_chunk[..., None, None] * states_permuted[:, :,
        # None, ...]).sum(dim=2)``) once a sequence spans two
        chunk_size=256,
        tie_word_embeddings=True,
    )
    hf_cfg._attn_implementation = "eager"
    model = Zamba2ForCausalLM(hf_cfg).eval()

    def t(a):
        return torch.from_numpy(np.array(a, np.float32))

    def norm(p):
        return t(1.0 + np.asarray(p["scale"], np.float32))

    lay = ref.layers(params["groups"])
    blocks = {}
    site = 0
    with torch.no_grad():
        model.model.embed_tokens.weight.copy_(t(params["embed"]["table"]))
        model.model.final_layernorm.weight.copy_(norm(params["final_norm"]))
        for i, (kind, lp) in enumerate(zip(KINDS, lay)):
            layer = model.model.layers[i]
            mixer_layer = layer.mamba_decoder if kind == "hybrid" else layer
            m, mp = mixer_layer.mamba, lp["mamba"]
            w_in = np.concatenate([mp[k]["w"] for k in ("z_proj", "x_proj", "bc_proj", "dt_proj")], axis=1)
            m.in_proj.weight.copy_(t(w_in.T))
            conv = np.concatenate([mp["conv_x_w"], mp["conv_bc_w"]], axis=1)
            m.conv1d.weight.copy_(t(conv.T[:, None, :]))
            m.conv1d.bias.copy_(t(np.concatenate([mp["conv_x_b"], mp["conv_bc_b"]])))
            m.dt_bias.copy_(t(mp["dt_bias"]))
            m.A_log.copy_(t(mp["A_log"]))
            m.D.copy_(t(mp["D"]))
            m.norm.weight.copy_(norm(mp["gate_norm"]))
            m.out_proj.weight.copy_(t(np.asarray(mp["out_proj"]["w"]).T))
            mixer_layer.input_layernorm.weight.copy_(norm(lp["ln1"]))
            if kind != "hybrid":
                continue
            b = site % s["num_mem_blocks"]
            blk, sp = layer.shared_transformer, params["shared"][f"block{b}"]
            assert blk.block_id == b
            blocks[b] = blk
            for name in ("q", "k", "v", "o"):
                getattr(blk.self_attn, f"{name}_proj").weight.copy_(t(np.asarray(sp["attn"][name]["w"]).T))
            blk.input_layernorm.weight.copy_(norm(sp["ln1"]))
            blk.pre_ff_layernorm.weight.copy_(norm(sp["ln2"]))
            ff, mlp, ad = blk.feed_forward, sp["mlp"], lp["adapter"]
            ff.gate_up_proj.weight.copy_(t(np.concatenate([mlp["gate"]["w"], mlp["up"]["w"]], axis=1).T))
            ff.down_proj.weight.copy_(t(np.asarray(mlp["down"]["w"]).T))
            adapter = ff.gate_up_proj_adapter_list[site]
            adapter[0].weight.copy_(t(np.asarray(ad["down"]["w"]).T))
            adapter[1].weight.copy_(t(np.concatenate([ad["gate"]["w"], ad["up"]["w"]], axis=1).T))
            layer.linear.weight.copy_(t(np.asarray(lp["linear"]["w"]).T))
            site += 1
        assert sorted(blocks) == [0, 1] and blocks[0] is not blocks[1]
        assert model.lm_head.weight.data_ptr() == model.model.embed_tokens.weight.data_ptr()
        got = model(input_ids=torch.from_numpy(np.asarray(tokens, np.int64)), use_cache=False).logits
    # both float32; transformers' chunked SSD against the stepped recurrence
    assert _gap(got.numpy(), want) < ATOL


def test_checkpoint_du_stores_each_shared_block_once(params):
    from repro.checkpoint import Checkpointer, flatten_tree
    from repro.core import Session, make_tpu_fleet_topology

    topo, _ = make_tpu_fleet_topology(pods=1, hosts_per_pod=1)
    with Session(topology=topo) as s:
        s.start_pilot_data(service_url="mem://cluster:pod0:host0/zamba2", affinity="cluster:pod0:host0")
        du = Checkpointer(s, run_name="zamba2-blocks").save(0, params)
        files = sorted(rel for rel in du.manifest if rel.startswith("params/"))
    block_leaves = [p for p, _ in flatten_tree({"b": params["shared"]["block0"]})]
    shared = [f for f in files if f.startswith("params/shared/")]
    assert len(shared) == 2 * len(block_leaves)
    assert {f.split("/")[2] for f in shared} == {"block0", "block1"}
    # the sites hold only their adapter and projection besides their mixer
    for key in ("pos6", "pos11"):
        site = {f.split("/")[3] for f in files if f.startswith(f"params/groups/{key}/")}
        assert site == {"adapter", "linear", "ln1", "mamba"}
    assert len(files) == len(flatten_tree(params))


def test_published_cut_sizes():
    """The benchmark's cut at published widths: its parameter count, and
    the cache of batch 8 and 4096 slots (KV at 2 sites, fp32 state in 13
    layers); mamba2-370m's chat cache beside it."""
    from repro.serving.engine import cache_bytes

    full = get_config("zamba2-7b")
    cut = dataclasses.replace(full, n_layers=13, pattern=full.layer_kinds()[:13])
    api = build_model(cut)
    shapes = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert held == cut.param_count() == 1_836_290_576
    cache = jax.eval_shape(lambda: api.init_cache(8, 4096))
    assert cache_bytes(cache) == {"kv_bytes": 1_879_048_192, "ssm_bytes": 200_105_984}
    kv_sites = sorted(k for k, c in cache["groups"].items() if "k" in c)
    assert kv_sites == ["pos11", "pos6"]
    mamba = build_model(get_config("mamba2-370m"))
    cache = jax.eval_shape(lambda: mamba.init_cache(8, 2048))
    assert cache_bytes(cache) == {"kv_bytes": 0, "ssm_bytes": 413_270_016}


def test_engine_init_records_cache_bytes(api, params):
    from repro import tracing
    from repro.serving import DecodeEngine

    before = tracing.counters()
    DecodeEngine(api, params, batch=2, max_len=16)
    rec = [r for r in tracing.spans() if r.name == "engine.init"][-1]
    kv = 2 * 2 * 2 * 16 * 4 * 32 * 4  # sites × K,V × batch × slots × heads × head × fp32
    ssm = 13 * 2 * (8 * 16 * 16 + 3 * (128 + 64)) * 4
    assert (rec.attrs["kv_bytes"], rec.attrs["ssm_bytes"]) == (kv, ssm)
    after = tracing.counters()
    for name, n in (("kv_bytes", kv), ("ssm_bytes", ssm)):
        assert after[name] - before.get(name, 0) == n


def _norm_before(x, params, eps):
    """``rms_norm`` as it was before groups: the whole last axis."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + params["scale"].astype(jnp.float32))
    return out.astype(x.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_group_norm_is_bit_equal_to_before(dtype):
    """With n_groups 1 the gated norm is today's, bit for bit, in the
    mixer's prefill and decode paths (mamba2-370m keeps its numerics)."""
    from repro.models import layers, mamba2

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 128)).astype(dtype)
    p = {"scale": (0.1 * jax.random.normal(jax.random.PRNGKey(2), (128,))).astype(dtype)}
    assert np.array_equal(np.asarray(layers.rms_norm(x, p, 1e-5)), np.asarray(_norm_before(x, p, 1e-5)))

    cfg = reduced(get_config("mamba2-370m"), param_dtype=dtype, compute_dtype=dtype)
    api = build_model(cfg)
    params = weights.make_weights(jax.eval_shape(api.init, jax.random.PRNGKey(0)), SEED)
    blk = jax.tree.map(lambda a: a[0], params["groups"]["pos0"])["mamba"]
    u = (0.5 * jax.random.normal(jax.random.PRNGKey(4), (2, 16, cfg.d_model))).astype(dtype)
    cache = jax.tree.map(lambda a: a[0], api.init_cache(2, 16)["groups"]["pos0"])

    def run():
        out, _ = jax.jit(lambda u: mamba2.mamba_block(blk, u, cfg))(u)
        step, _ = jax.jit(lambda u: mamba2.mamba_decode_step(blk, u[:, :1], cache, cfg))(u)
        return np.asarray(out), np.asarray(step)

    got = run()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mamba2, "rms_norm", lambda x, p, eps, groups=1: _norm_before(x, p, eps))
        before = run()
    for g, b in zip(got, before):
        assert np.array_equal(g, b)


def test_grouped_norm_normalizes_each_group():
    from repro.models import layers

    x = jax.random.normal(jax.random.PRNGKey(5), (3, 64))
    x = x * jnp.where(jnp.arange(64) < 32, 1.0, 10.0)  # groups of unlike scale
    p = {"scale": jnp.zeros((64,))}
    got = layers.rms_norm(x, p, 1e-6, groups=2)
    want = jnp.concatenate([_norm_before(x[:, :32], {"scale": p["scale"][:32]}, 1e-6),
                            _norm_before(x[:, 32:], {"scale": p["scale"][32:]}, 1e-6)], axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    assert _gap(layers.rms_norm(x, p, 1e-6), np.asarray(want)) > 0.1
