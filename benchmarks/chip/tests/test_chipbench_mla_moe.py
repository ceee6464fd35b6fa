"""The ``mla_moe`` architecture (DeepSeek-V2, ``archs/mla_moe.py`` and
``references/mla_moe.py``): a tiny cell served through the harness on the
CPU against the reference, the program's prefill and decode against the
reference's logits, each with a planted fault that fails it; the counts
against hand sums at the published widths; and the decode step at 32 slots x
4096 compiled for a described TPU v5e (no chip).

The v5e topology is described inside a module fixture, never at import."""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chipbench_tiny as tiny
from chipbench import harness, spec

import repro.models.attention as attention_mod
import repro.models.moe as moe_mod
from repro.models import model as model_lib
from repro.serve.engine import EngineConfig, ServeEngine

PUBLISHED = json.loads(
    (tiny.BENCH / "configs/deepseek-v2-lite-e8.json").read_text())
# The published file at small widths, every mechanism kept: a dense layer
# then two expert layers; 4 of 16 experts held, top-3, 2 shared experts;
# query without compression; YaRN at factor 40, its original context cut
# to 64 positions so that it turns the rope at the tiny cell's lengths.
TINY = dict(
    PUBLISHED, name="tiny-mla", num_hidden_layers=3, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    intermediate_size=96, moe_intermediate_size=16, n_routed_experts=4,
    num_experts_per_tok=3, vocab_size=512, published={"n_routed_experts": 16},
    rope_scaling=dict(PUBLISHED["rope_scaling"],
                      original_max_position_embeddings=64))
# Set from CPU readings at this size, as the chip cell's limit is: the
# program in bfloat16 read at most 0.023 on five seeds (the gap of a served
# token's logit below the float32 reference's best); the fp8 control read at
# least 0.40, and each planted fault below at least 1.46 (seeds 1-3).
LIMITS = {"served_logit_gap": 0.12}


def _bench(root) -> spec.Bench:
    """A bench root with the real architectures, readers and reference, the
    tiny configuration, chipbench_tiny's serving mix, and one cell."""
    b = tiny.bench(root)
    (root / "configs/tiny-mla.json").write_text(json.dumps(TINY))
    (root / "limits/tiny-mla.serve.json").write_text(json.dumps(LIMITS))
    b.spec["workloads"] = [{"name": "tiny-mla.serve", "config": "tiny-mla",
                            "traffic": "tiny-serve", "chips": 1,
                            "why": "CPU test"}]
    for m in b.spec["end_to_end"] + b.spec["per_layer"]:
        if "workloads" in m and "serve" in m["name"]:
            m["workloads"] = ["tiny-mla.serve"]
    return b


def _drop_shared(real):
    def share(params, x, spec_, act="swiglu"):
        return real({k: v for k, v in params.items() if k != "shared"}, x,
                    spec_, act)
    return share


def _no_yarn_scale(cfg):
    return cfg.mla.qk_head_dim ** -0.5


FAULTS = {"shared_expert_dropped": (moe_mod, "moe_share_ffn", _drop_shared),
          "yarn_scale_left_out": (attention_mod, "mla_scale",
                                  lambda real: _no_yarn_scale)}


def _run(root, capsys, seed=7):
    harness.main(["--workload", "tiny-mla.serve", "--seed", str(seed),
                  "--seconds", "1", "--trace", "0"], bench=_bench(root),
                 allow_cpu=True, peak=tiny.PEAK)
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_cell_serves_against_the_reference(tmp_path, capsys):
    res = _run(tmp_path, capsys, seed=2 ** 31 + 99)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_cell_with_fault_is_not_correct(tmp_path, capsys, monkeypatch, fault):
    mod, name, make = FAULTS[fault]
    monkeypatch.setattr(mod, name, make(getattr(mod, name)))
    res = _run(tmp_path, capsys)
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_is_not_correct(tmp_path):
    ctx, c = harness.build(_bench(tmp_path), "tiny-mla.serve", 3,
                           allow_cpu=True, peak=tiny.PEAK)
    c.setup(1.0)
    c.window()
    c.release()
    assert c.readings()["served_logit_gap"] <= LIMITS["served_logit_gap"]
    control = c.control_readings()["control_fp8"]["served_logit_gap"]
    assert control > LIMITS["served_logit_gap"]


def _tiny_f32(tmp_path):
    b = _bench(tmp_path)
    conf = dict(b.config("tiny-mla"), torch_dtype="float32")
    arch, ref = b.architecture(conf)
    d = arch.dims(conf)
    cfg = arch.program_config(conf)
    params = jax.jit(functools.partial(ref.make_params, d,
                                       dtype=jnp.float32))(jax.random.PRNGKey(5))
    return d, cfg, ref, params


# float32 on both sides: the program's absorbed decode and blocked sums
# differ from the reference's naive order by float32 rounding alone, 3.5e-6
# on logits up to 4.8 after prefill (CPU, this seed); 1e-4 leaves that
# room about 30 times, and each planted fault misses it a hundredfold.
F32_TOL = 1e-4


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_prefill_and_decode_match_reference_logits(tmp_path, monkeypatch,
                                                   fault):
    """Prefill of 20 tokens, then 12 decode steps through the cache, each
    position's logits against the reference's full forward pass."""
    d, cfg, ref, params = _tiny_f32(tmp_path)
    if fault:
        mod, name, make = FAULTS[fault]
        monkeypatch.setattr(mod, name, make(getattr(mod, name)))
    B, P, N, T = 2, 20, 12, 40
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, P + N), 0,
                              d.vocab_size)
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(params, toks, d)
        want = np.asarray(jnp.einsum("bsd,dv->bsv", h,
                                     params["lm_head"]))
    logits, caches = model_lib.prefill(params, cfg, toks[:, :P])
    cap = model_lib.init_cache(cfg, B, T)
    caches = jax.tree.map(
        lambda c, full: jnp.pad(c, [(0, f - s) for s, f in
                                    zip(c.shape, full.shape)]), caches, cap)
    got = [np.asarray(logits)]
    step = jax.jit(model_lib.decode_step, static_argnums=1)
    for i in range(N - 1):
        logits, caches, _ = step(params, cfg, toks[:, P + i:P + i + 1],
                                 caches, jnp.full((B,), P + i, jnp.int32))
        got.append(np.asarray(logits))
    err = np.max(np.abs(np.stack(got, 1) - want[:, P - 1:P + N - 1]))
    if fault:
        assert err > 100 * F32_TOL, f"{fault} not seen: {err}"
    else:
        assert err < F32_TOL


def test_param_tree_is_the_programs(tmp_path):
    """The reference's weights have the program's tree, shapes and dtypes."""
    d, cfg, ref, params = _tiny_f32(tmp_path)
    want = model_lib.init_params_shape(cfg)
    got = jax.eval_shape(lambda: params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.leaves(got) == jax.tree.leaves(want)


@functools.cache
def _published():
    b = spec.Bench()
    arch, _ = b.architecture(PUBLISHED)
    return arch, arch.dims(PUBLISHED), arch.program_config(PUBLISHED)


def test_counts_at_published_widths():
    a, d, cfg = _published()
    assert (d.n_experts, d.n_held, d.top_k, d.d_ff_shared) == (64, 8, 6, 2816)
    # W_q 2048 x 16*192, W_kva 2048 x 576, W_kvb 512 x 16*256, W_o 2048 x 2048
    att = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert a.attention_matmul_params(d) == att == 13_762_560
    expert = 3 * 2048 * 1408
    moe = 2048 * 64 + 8 * expert + 3 * 2048 * 2816
    assert a.moe_layer_params(d) == moe == 86_638_592
    norms = 27 * (2 * 2048 + 512) + 2048
    total = (2 * 102400 * 2048 + norms + 27 * att + 3 * 2048 * 10944
             + 26 * moe)
    assert a.param_count(d) == total == 3_110_989_312
    assert a.param_count(d) == model_lib.count_params(cfg)
    assert a.weight_bytes(d) == 2 * total + 2 * 26 * 2048 * 64
    assert a.cache_bytes_per_token(d) == 2 * 27 * 576 == 31104
    # a token: 27 attentions, the dense MLP, 26 x (router, shared, and
    # 6 * 8 / 64 = 0.75 of an expert)
    per = 27 * att + 3 * 2048 * 10944 + 26 * (2048 * 64 + 0.75 * expert
                                              + 3 * 2048 * 2816)
    assert a.token_matmul_params(d) == pytest.approx(per)
    head = 2048 * 102400
    # decode from 100: queries at 100 and 101 see 101 and 102 latent keys,
    # 2 * 16 * (512 + 64 + 512) a key a layer
    assert a.decode_flops(d, 100, 2) == pytest.approx(
        2 * 2 * (per + head) + 27 * 2 * 16 * 1088 * (101 + 102))
    # a prefill of 64: per-head keys and values, 2 * 16 * (192 + 128)
    assert a.prefill_flops(d, 64) == pytest.approx(
        2 * 64 * per + 27 * 2 * 16 * 320 * (64 * 65 // 2) + 2 * head)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_decode_32x4096_compiles_in_place(one_chip):
    """The engine's decode program at the cell's size, the cache donated and
    the routed-token count kept: it fits one chip, the whole cache is
    aliased, and its temporaries are smaller than one expert layer's latent
    cache, so no whole latent stack is copied per step."""
    _, _, cfg = _published()

    def on(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    B, T = 32, 4096
    params = on(model_lib.init_params_shape(cfg))
    caches = on(model_lib.cache_struct(cfg, B, T))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((B,), jnp.float32, sharding=one_chip)
    routed = jax.ShapeDtypeStruct((cfg.moe.n_held,), jnp.float32,
                                  sharding=one_chip)
    engine = ServeEngine(cfg, None, EngineConfig(slots=B, max_seq_len=T,
                                                 monitor=False))
    compiled = engine._decode.lower(params, tok, caches, lens, live,
                                    routed).compile()
    m = compiled.memory_analysis()
    got = {k: int(getattr(m, f"{k}_size_in_bytes")) for k in
           ("argument", "output", "temp", "alias")}
    print(f"memory_analysis {got}")
    cache = sum(int(np.prod(x.shape)) * x.dtype.itemsize
                for x in jax.tree.leaves(caches))
    assert cache == 27 * B * T * 576 * 2
    assert got["alias"] == cache
    one_layer = B * T * 512 * 2
    assert got["temp"] < one_layer
    assert got["argument"] + got["output"] + got["temp"] - got["alias"] \
        < 16 * 1024 ** 3
