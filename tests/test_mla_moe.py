"""The ``mla_moe`` backbone (Moonlight-16B-A3B's DeepSeek-V3 block) against
its plain reference, ``bench/references/moonlight_mla_moe.py``.

CPU, float32 weights from the reference's seeded ``make_weights``, at a
small size (d 64, 4 heads, latent 32, rotary 8, 8 routed experts, top-2,
4 of them held here).  Program and reference compute the same float32
mathematics at ``"highest"`` matmul precision and differ only in the order
of their sums (chunked online softmax, grouped matmuls over sorted rows,
float32 accumulation of each token's choices), so the comparisons are held
to ``RTOL`` -- a hundred float32 ulps; a bfloat16 step in either is ~1e-3.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench")]

from harness import spec  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data.synthetic import synthetic_document  # noqa: E402
from repro.embeddings import EncoderStage  # noqa: E402
from repro.embeddings.serving import _embed_batch  # noqa: E402
from repro.models import attention as attn  # noqa: E402
from repro.models import mlp as mlp_mod  # noqa: E402
from repro.models.common import rmsnorm  # noqa: E402
from repro.obs import Observability  # noqa: E402

REF = spec.load_module(ROOT / "bench" / "references" / "moonlight_mla_moe.py")
RTOL = 1e-5
CONFIG = json.loads((ROOT / "bench" / "configs" / "es-cobi-moonlight.json")
                    .read_text())


def small(**moe):
    cfg = get_config("moonlight-16b-a3b").reduced()
    if moe:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, **moe))
    return cfg


def arch_keys(cfg) -> dict:
    """The program's configuration in the configuration file's terms."""
    m, a = cfg.moe, cfg.mla
    return {"family": cfg.family, "first_k_dense": cfg.first_k_dense,
            "embed_scale": cfg.embed_scale,
            "kv_lora_rank": a.kv_lora_rank,
            "qk_nope_head_dim": a.qk_nope_head_dim,
            "qk_rope_head_dim": a.qk_rope_head_dim,
            "v_head_dim": a.v_head_dim, "kv_norm_eps": a.kv_norm_eps,
            "num_experts": m.num_experts, "experts_held": m.held,
            "expert_shard": m.expert_shard, "top_k": m.top_k,
            "d_ff_expert": m.d_ff_expert, "d_ff_shared": m.d_ff_shared,
            "routed_scaling": m.routed_scaling}


def enc_of(cfg) -> dict:
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "max_seq_len": cfg.max_seq_len, "param_dtype": cfg.param_dtype,
            "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "act": cfg.act, "gated_mlp": cfg.gated_mlp,
            "arch_keys": arch_keys(cfg)}


def ref_items(enc: dict) -> tuple:
    return tuple((k, enc[k]) for k in REF.ENC_KEYS) + tuple(
        (k, enc["arch_keys"][k]) for k in REF.ARCH_KEYS)


def flat_items(enc: dict) -> dict:
    return dict(ref_items(enc))


def weights(cfg, seed=3):
    return REF.make_weights(enc_of(cfg), jax.random.key(seed), "float32")


def layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def docs_tokens(docs, length, max_len=128):
    rows = [REF.tokenize(d, length, max_len) for d in docs]
    return (jnp.asarray(np.stack([r[0] for r in rows])),
            jnp.asarray(np.stack([r[1] for r in rows])))


DOCS = [synthetic_document(1, 3), synthetic_document(2, 2),
        synthetic_document(3, 1)]


def close(a, b):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=RTOL,
                               atol=RTOL * float(np.abs(np.asarray(b)).max()))


@pytest.mark.parametrize("chunk", [None, 32])
def test_latent_attention_matches_reference(chunk):
    """The dense block with its feed-forward zeroed is x + MLA(norm(x)); at
    ``chunk`` 32 the program takes the chunked online softmax."""
    cfg = small().replace(attn_chunk=chunk)
    w = layer(weights(cfg)["dense"], 0)
    w["mlp"] = jax.tree.map(jnp.zeros_like, w["mlp"])
    x = jax.random.normal(jax.random.key(7), (2, 64, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(w["attn_norm"], x, eps=cfg.norm_eps)
        got = x + attn.mla_attention(w["attn"], cfg, h, pos)
        want = REF._layer(ref_items(enc_of(cfg)), w, x, "none")
    close(got, want)


@pytest.mark.parametrize("chunk", [None, 32])
def test_embeddings_match_reference(chunk):
    cfg = small().replace(attn_chunk=chunk)
    enc, w = enc_of(cfg), weights(cfg)
    tokens, segs = docs_tokens(DOCS, 128)
    with jax.default_matmul_precision("highest"):
        got = _embed_batch(cfg, w, tokens, segs, 8)[0]
        want = REF.embed(enc, w, tokens, segs, 8)
    close(got, want)
    assert float(jnp.abs(got).max()) > 0


def test_router_choices_match_reference():
    cfg = small()
    m = layer(weights(cfg)["layers"], 0)["moe"]
    h = jax.random.normal(jax.random.key(8), (512, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        w, idx = mlp_mod.route_sigmoid(m, cfg, h)
        w_ref, idx_ref = REF.route(flat_items(enc_of(cfg)), m, h)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    close(w, w_ref)
    # the bias moves choices: without it some tokens choose otherwise
    _, plain = jax.lax.top_k(jax.nn.sigmoid(h @ m["router"]), cfg.moe.top_k)
    assert (np.sort(np.asarray(plain), -1) != np.sort(np.asarray(idx), -1)).any()


def test_dropless_when_every_token_picks_one_expert():
    """A bias that sends every token to held expert 1: no choice is dropped
    and the layer still equals the reference."""
    cfg = small()
    m = layer(weights(cfg)["layers"], 0)["moe"]
    m["router_bias"] = m["router_bias"].at[1].add(100.0)
    h = jax.random.normal(jax.random.key(9), (2, 96, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, route = mlp_mod.moe_dropless(m, cfg, h)
        want = REF.moe(flat_items(enc_of(cfg)), m, h)
    ex = np.asarray(route["experts"])
    assert (ex == 1).any(-1).all()  # every token chose expert 1
    held = (ex >= 0) & (ex < cfg.moe.held)
    np.testing.assert_array_equal(np.asarray(route["computed"]), held)
    close(y, want)


def test_expert_shares_add_up_to_the_uncut_layer():
    """8 experts held as 2 shares of 4: the shares' routed parts, with the
    shared experts counted once, add up to the reference layer that holds
    all 8."""
    whole = small(experts_held=8)
    m = layer(weights(whole)["layers"], 0)["moe"]
    h = jax.random.normal(jax.random.key(10), (2, 64, whole.d_model))
    parts = []
    with jax.default_matmul_precision("highest"):
        shared = mlp_mod.mlp_apply(m["shared"], whole, h)
        for shard in (0, 1):
            cfg = small(experts_held=4, expert_shard=shard)
            mine = dict(m, **{k: m[k][4 * shard:4 * shard + 4]
                              for k in ("w_in", "w_gate", "w_out")})
            y, route = mlp_mod.moe_dropless(mine, cfg, h)
            parts.append(y - shared)
            assert bool(route["computed"].any())
        want = REF.moe(flat_items(enc_of(whole)), m, h)
    close(parts[0] + parts[1] + shared, want)


def rows(docs, length, n_rows):
    """A launch's (tokens, segs): ``docs`` then PAD rows, ``n_rows`` in all,
    as the stage pads its batches."""
    tokens, segs = docs_tokens(docs, length, length)
    pad = n_rows - len(docs)
    return (jnp.concatenate([tokens, jnp.zeros((pad, length), jnp.int32)]),
            jnp.concatenate([segs, jnp.full((pad, length), -1, jnp.int32)]))


def test_same_document_is_bit_identical_alone_with_mates_and_padded():
    """The stage's contract on the routed backbone: alone, beside
    batch-mates, in a larger row bucket and at a longer length bucket, a
    document's embeddings are the same bits."""
    cfg = small().replace(param_dtype="bfloat16")
    w = REF.make_weights(enc_of(cfg), jax.random.key(4), "bfloat16")
    target, n = DOCS[0], len(DOCS[0])  # 238 tokens: the 256 bucket
    alone = _embed_batch(cfg, w, *rows([target], 256, 4), 8)[0][0, :n]
    for docs, length, n_rows, i in (([DOCS[1], target, DOCS[2]], 256, 4, 1),
                                    ([DOCS[2], target], 256, 8, 1),
                                    ([target], 512, 4, 0)):
        got = _embed_batch(cfg, w, *rows(docs, length, n_rows), 16)[0][i, :n]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
    # and through the stage, batched with mates in one launch
    stage = EncoderStage(cfg, w, max_len=256, linger=0.1)
    futs = [stage.submit(d) for d in (DOCS[1], target, DOCS[2])]
    got = futs[1].result(timeout=120)
    assert futs[1].receipt().batch_jobs >= 2  # shared its launch
    stage.close()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))


def test_route_counts_cover_the_real_tokens():
    cfg = small()
    w = weights(cfg)
    tokens, segs = docs_tokens(DOCS, 128)
    _, counts = _embed_batch(cfg, w, tokens, segs, 8)
    real = int((tokens != 0).sum())
    n_moe = cfg.n_layers - cfg.first_k_dense
    assert counts["held"].shape == (n_moe, cfg.moe.held)
    per_layer = np.asarray(counts["held"]).sum(1) + np.asarray(counts["absent"])
    np.testing.assert_array_equal(per_layer, real * cfg.moe.top_k)
    np.testing.assert_array_equal(np.asarray(counts["dropped"]), 0)
    assert int(np.asarray(counts["held"]).sum()) > 0


def test_stage_meters_routing_in_a_span_and_counters():
    cfg = small()
    obs = Observability(tracing=True)
    stage = EncoderStage(cfg, weights(cfg), max_len=128, obs=obs)
    stage.submit(DOCS[0], tag=None).result(timeout=120)
    stage.close()
    spans = [r for r in obs.tracer.records() if r.get("kind") == "span"]
    route = [r for r in spans if r["name"] == "encoder.route"]
    batch = [r for r in spans if r["name"] == "encoder.batch"]
    assert len(route) == 1 and route[0]["parent"] == batch[0]["id"]
    assert route[0]["track"] == "encoder"
    a = route[0]["attrs"]
    assert a["dropped"] == 0 and a["held_choices"] > 0
    assert a["expert_max"] >= a["expert_mean"] > 0
    reg = obs.registry
    choices = reg.counter("encoder_expert_choices_total", labels=("held",))
    assert choices.labels(held="true").value == a["held_choices"]
    assert choices.labels(held="false").value == a["absent_choices"]
    assert reg.counter("encoder_dropped_choices_total").value == 0


def test_dense_stage_emits_no_routing():
    obs = Observability(tracing=True)
    stage = EncoderStage.tiny(obs=obs)
    out = _embed_batch(stage.cfg, stage.params, *docs_tokens(DOCS, 256, 256), 8)
    assert not isinstance(out, tuple)
    stage.submit(DOCS[0]).result(timeout=120)
    stage.close()
    names = {r["name"] for r in obs.tracer.records()}
    assert "encoder.batch" in names and "encoder.route" not in names
    assert obs.registry.counter("encoder_dropped_choices_total").value == 0


def test_prewarm_and_grouped_drains_keep_each_document_bits():
    """The routed stage warms every (batch, length, segments) shape asked
    for, and a burst drained in grouped launches gives each document the
    bits it gets alone."""
    cfg = small()
    stage = EncoderStage(cfg, weights(cfg), max_len=128, linger=0.1)
    assert stage.prewarm(lengths=[128], batches=[4, 8, 16],
                         segments=[8]) == 3
    alone = np.asarray(stage.submit(DOCS[0]).result(timeout=120))
    futs = [stage.submit(DOCS[i % 3]) for i in range(10)]
    out = [f.result(timeout=120) for f in futs]
    assert max(f.receipt().batch_jobs for f in futs) > 1
    np.testing.assert_array_equal(np.asarray(out[0]), alone)
    np.testing.assert_array_equal(np.asarray(out[3]), alone)
    stage.close()


@pytest.mark.parametrize("name", ["moonlight-16b-a3b", "qwen2-moe-a2.7b"])
def test_family_decides_the_router_parameters(name):
    """``mla_moe`` layers carry the choice bias and add their shared
    experts ungated; the GShard ``moe`` family gates its shared expert."""
    cfg = get_config(name).reduced()
    p = jax.eval_shape(lambda: mlp_mod.moe_init(jax.random.key(0), cfg))
    routed = cfg.family == "mla_moe"
    assert ("router_bias" in p) == routed
    assert ("shared_gate" in p) == (not routed and bool(cfg.moe.d_ff_shared))


def test_registry_entry_matches_the_configuration_file():
    cfg = get_config("moonlight-16b-a3b")
    enc = CONFIG["encoder"]
    assert enc["arch"] == cfg.name
    assert enc["arch_keys"] == arch_keys(cfg)
    # the catalog's published numbers and the file's reduced ones
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"],
            CONFIG["num_hidden_layers"], CONFIG["intermediate_size"]) == (
        cfg.d_model, cfg.n_heads, cfg.n_layers, cfg.d_ff)
    assert (CONFIG["kv_lora_rank"], CONFIG["qk_nope_head_dim"],
            CONFIG["qk_rope_head_dim"], CONFIG["v_head_dim"]) == (
        cfg.mla.kv_lora_rank, cfg.mla.qk_nope_head_dim,
        cfg.mla.qk_rope_head_dim, cfg.mla.v_head_dim)
    assert (CONFIG["num_experts_per_tok"], CONFIG["moe_intermediate_size"],
            CONFIG["n_shared_experts"] * CONFIG["moe_intermediate_size"],
            CONFIG["routed_scaling_factor"], CONFIG["first_k_dense_replace"]) == (
        cfg.moe.top_k, cfg.moe.d_ff_expert, cfg.moe.d_ff_shared,
        cfg.moe.routed_scaling, cfg.first_k_dense)
    assert CONFIG["n_routed_experts"] == cfg.moe.held == 8
    assert CONFIG["published"]["n_routed_experts"] == cfg.moe.num_experts
    assert CONFIG["published"]["vocab_size"] == cfg.vocab_size
    assert CONFIG["scoring_func"] == "sigmoid" and cfg.family == "mla_moe"
    assert CONFIG["norm_topk_prob"] and CONFIG["n_group"] == CONFIG["topk_group"] == 1
    assert (enc["vocab_size"], enc["max_seq_len"]) == (
        CONFIG["vocab_size"], CONFIG["max_position_embeddings"])
    assert enc["rope_theta"] == CONFIG["rope_theta"] == cfg.rope_theta


def test_decode_is_refused():
    cfg = small()
    from repro.models import forward
    w = weights(cfg)
    tokens = jnp.ones((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError):
        forward(cfg, w, tokens, mode="prefill")
