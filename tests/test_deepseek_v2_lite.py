"""DeepSeek-V2-Lite's mechanisms on the served path: latent attention
without q-LoRA, YaRN, a leading dense layer and the chip-share expert layer.

The engine is checked against the plain float32 reference that the chip
benchmark keeps with the configuration (``benchmarks/chip/configs/
deepseek-v2-lite.py``), on seeded random weights at smoke widths: prefill,
then decode through the lane cache with lanes at different positions, must
give the reference's full-forward logits."""

import copy
import sys
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.deploy import ServeEngine, ServeRequest
from repro.core.deploy.engine import _jitted
from repro.models.attention import mla_scale
from repro.models.layers import swiglu, yarn_freqs, yarn_mscale
from repro.models.moe import (DENSE_MAX_ROWS, _route, init_moe, moe_dense,
                              moe_share)
from repro.models.transformer import (decode_step, init_cache, init_params,
                                      prefill)

BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from chipbench import cells  # noqa: E402


def _smoke_cell():
    """The benchmark's configuration document and its ModelConfig at smoke
    widths, holding experts 4-7 of 8 (the second of two shares)."""
    cfg = smoke_config("deepseek-v2-lite").scaled(experts_held=4,
                                                  expert_block=1)
    cell = cells.load_cell("deepseek-v2-lite.batch")
    doc = copy.deepcopy(cell.config)
    doc.update(num_hidden_layers=cfg.n_layers, hidden_size=cfg.d_model,
               num_attention_heads=cfg.n_heads,
               qk_nope_head_dim=cfg.qk_nope_dim,
               qk_rope_head_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
               kv_lora_rank=cfg.kv_lora_rank, intermediate_size=cfg.d_ff,
               moe_intermediate_size=cfg.moe_d_ff,
               n_routed_experts=cfg.experts_held,
               n_shared_experts=cfg.n_shared_experts,
               num_experts_per_tok=cfg.top_k, vocab_size=cfg.vocab)
    doc["rope_scaling"]["original_max_position_embeddings"] = \
        cfg.yarn_original_max_pos
    doc["deployment"].update(published_n_routed_experts=cfg.n_experts,
                             expert_block=cfg.expert_block)
    return cell.reference, doc, cfg


class _Recording(ServeEngine):
    """Keeps each lane's decode logits with the position they follow."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.seen: dict[str, list] = {}

    def _decode_complete(self, pending):
        for _, active, logits, _ in pending:
            rows = np.asarray(logits)
            for i, lane in active:
                self.seen.setdefault(lane.req.uid, []).append(
                    (lane.index, rows[i]))
        super()._decode_complete(pending)


def test_engine_matches_the_reference_through_the_lane_cache():
    ref, doc, cfg = _smoke_cell()
    weights = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        ref.make_weights(doc, jax.random.PRNGKey(7), jax.devices()[0]))
    rng = np.random.default_rng(3)
    lens = [(5, 8), (9, 6), (12, 10), (7, 5), (3, 9)]
    reqs = [ServeRequest(uid=f"r{i}", max_new_tokens=n,
                         tokens=rng.integers(0, cfg.vocab, p).astype(
                             np.int32))
            for i, (p, n) in enumerate(lens)]
    eng = _Recording(cfg, weights, max_len=24, max_slots=3,
                     prefill_chunk=1)
    done = {r.uid: r for r in eng.run(reqs, stagger=1)}
    pre, _ = _jitted(cfg)
    for req in reqs:
        P = len(req.tokens)
        seq = np.concatenate([req.tokens, done[req.uid].tokens[:-1]])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref._logits(doc, weights, jnp.asarray(seq),
                                          False))
        got, _ = pre(weights, {"tokens": req.tokens[None],
                                  "positions": np.arange(P)[None]})
        np.testing.assert_allclose(np.asarray(got)[0], want[P - 1],
                                   rtol=1e-4, atol=1e-4)
        rows = eng.seen[req.uid]
        assert [i for i, _ in rows] == list(range(P, len(seq)))
        for i, row in rows:
            np.testing.assert_allclose(row, want[i], rtol=1e-4, atol=1e-4,
                                       err_msg=f"{req.uid} at {i}")


def _sixteen_experts(**kw):
    from repro.models.common import ModelConfig
    cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                      n_experts=16, n_shared_experts=1, top_k=4,
                      moe_d_ff=24, **kw)
    return cfg, init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)


def _takes_grouped_form(p, cfg, x):
    return "ragged_dot" in str(jax.make_jaxpr(
        lambda x: moe_share(p, cfg, x))(x))


# 10 rows take the dense form, 160 the grouped one (DENSE_MAX_ROWS is 128)
@pytest.mark.parametrize("seq", [5, 80])
@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_chip_shares_add_up_to_the_uncut_layer(norm_topk_prob, seq):
    """16 experts as 4 shares of 4: the shares' outputs, with the shared
    expert (which every chip computes) counted once, add up to the uncut
    layer, and every routed pair lands in exactly one share."""
    cfg, p = _sixteen_experts(norm_topk_prob=norm_topk_prob)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 32))
    assert _takes_grouped_form(p, cfg, x) == (2 * seq > DENSE_MAX_ROWS)
    shared = swiglu(x, p["sh_gate"], p["sh_up"], p["sh_down"])
    parts, n_here = [], 0
    for b in range(4):
        sl = slice(4 * b, 4 * b + 4)
        pb = dict(p, w_gate=p["w_gate"][sl], w_up=p["w_up"][sl],
                  w_down=p["w_down"][sl])
        y, n = moe_share(pb, cfg.scaled(experts_held=4, expert_block=b), x)
        parts.append(y - shared)
        assert n.shape == (2,)                  # one count per row of x
        n_here = n_here + np.asarray(n)
    np.testing.assert_allclose(sum(parts) + shared, moe_dense(p, cfg, x),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(n_here, [seq * 4, seq * 4])


@pytest.mark.parametrize("block", [0, 3])
def test_dense_and_grouped_forms_agree(block):
    """The same 160 rows through the grouped form at once and through the
    dense form in two calls of 80: a row's output and count depend on that
    row alone, so the two forms give the same output and ``n_here``."""
    cfg, p = _sixteen_experts()
    cfg = cfg.scaled(experts_held=4, expert_block=block)
    sl = slice(4 * block, 4 * block + 4)
    p = dict(p, w_gate=p["w_gate"][sl], w_up=p["w_up"][sl],
             w_down=p["w_down"][sl])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 80, 32))
    halves = x[:, :40], x[:, 40:]
    assert _takes_grouped_form(p, cfg, x)
    assert not any(_takes_grouped_form(p, cfg, h) for h in halves)
    y, n = moe_share(p, cfg, x)
    parts = [moe_share(p, cfg, h) for h in halves]
    np.testing.assert_allclose(
        y, jnp.concatenate([yh for yh, _ in parts], 1), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(n, sum(np.asarray(nh) for _, nh in parts))
    assert 0 < int(np.sum(n)) < 160 * cfg.top_k


def test_decode_computes_the_held_experts_densely_and_prefill_grouped():
    """The served programs' jaxprs: a decode of a few lanes holds no grouped
    product, a prefill of more than DENSE_MAX_ROWS tokens does."""
    cfg = smoke_config("deepseek-v2-lite").scaled(experts_held=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pre, dec = _jitted(cfg)
    lanes, T = 4, DENSE_MAX_ROWS + 8
    tb = {"tokens": jnp.zeros((lanes, 1), jnp.int32),
          "positions": jnp.zeros((lanes, 1), jnp.int32)}
    decode = str(jax.make_jaxpr(dec)(params, tb, init_cache(cfg, lanes, T),
                                     jnp.zeros((lanes,), jnp.int32)))
    prompt = {"tokens": jnp.zeros((1, T), jnp.int32),
              "positions": jnp.arange(T)[None]}
    assert "ragged_dot" not in decode
    assert "ragged_dot" in str(jax.make_jaxpr(pre)(params, prompt))


@pytest.mark.parametrize("held", [0, 4])
def test_engine_counts_the_routed_pairs_of_busy_lanes(held):
    """With a recorder on, the engine counts each decode execution's pairs
    over its busy lanes only, though idle lanes are computed too (and count
    among the dense form's rows): holding every expert, every pair of every
    decoded token lands here."""
    from repro.core.spans import Spans
    cfg = smoke_config("deepseek-v2-lite").scaled(experts_held=held)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    reqs = [ServeRequest(uid=f"r{i}", max_new_tokens=n,
                         tokens=rng.integers(0, cfg.vocab, p).astype(
                             np.int32))
            for i, (p, n) in enumerate([(5, 8), (9, 3), (4, 6), (7, 2)])]
    spans = Spans()
    eng = _Recording(cfg, params, max_len=24, max_slots=3, prefill_chunk=1,
                     spans=spans)
    eng.run(reqs, stagger=1)
    steps = sum(len(rows) for rows in eng.seen.values())
    assert steps < 3 * eng.n_decode_batches          # idle lanes were run
    c, n_moe = spans.counters, cfg.n_moe_layers
    assert c["moe_pairs"] == steps * cfg.top_k * n_moe
    assert c["moe_expert_slots"] == \
        eng.n_decode_batches * cfg.n_experts_here * n_moe
    # every decode of 3 lanes takes the dense form, idle lanes included
    assert c["moe_rows_dense"] == \
        eng.n_decode_batches * 3 * cfg.n_experts_here * n_moe
    if held:
        assert 0 < c["moe_pairs_here"] < c["moe_pairs"]
    else:
        assert c["moe_pairs_here"] == c["moe_pairs"]


def test_yarn_frequencies_and_scale_by_hand():
    """dim 64, theta 1e4, factor 40 over 4096 positions, beta 32 and 1:
    ``low`` = floor(64 ln(4096 / (64 pi)) / (2 ln 1e4)) = floor(10.47) = 10,
    ``high`` = ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = ceil(22.51) = 23;
    mscale(40, 0.707) = 0.0707 ln 40 + 1 = 1.260804, and the softmax scale
    192 ** -0.5 * 1.260804 ** 2 = 0.114721."""
    base = 1e4 ** -(np.arange(0, 64, 2) / 64)
    got = yarn_freqs(64, 1e4, 40.0, 4096, 32.0, 1.0)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, base * (1 - ramp) + base / 40 * ramp,
                               rtol=1e-12)
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-12)
    assert 0 < ramp[11] < ramp[22] < 1
    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.260804, abs=1e-6)
    assert mla_scale(get_config("deepseek-v2-lite")) == pytest.approx(
        0.114721, abs=1e-6)
    # without YaRN the scale is the plain (nope + rope) ** -0.5
    assert mla_scale(smoke_config("deepseek-v3-671b")) == pytest.approx(
        (16 + 8) ** -0.5)


def test_gates_are_left_unrenormalised():
    cfg = get_config("deepseek-v2-lite")
    router = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))
    w, idx = _route(x, router, cfg)
    gates = jax.nn.softmax(x @ router, -1)
    np.testing.assert_allclose(
        w, jnp.take_along_axis(gates, idx, -1), rtol=1e-5)
    assert float(jnp.max(jnp.sum(w, -1))) < 1.0
    w_norm, _ = _route(x, router, cfg.scaled(norm_topk_prob=True))
    np.testing.assert_allclose(jnp.sum(w_norm, -1), 1.0, rtol=1e-5)


def test_mla_without_q_lora_decode_equals_prefill():
    cfg = smoke_config("deepseek-v2-lite")
    params = init_params(cfg, jax.random.PRNGKey(0))
    attn = params["layers"]["attn"]
    assert "wq" in attn and "wq_a" not in attn and "q_norm" not in attn
    assert params["dense_layers"]["mlp"]["gate"].shape == (1, 64, 160)
    B, T = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab)
    last, pre_caches = prefill(params, {"tokens": toks}, cfg)
    caches = init_cache(cfg, B, T)
    step = jax.jit(partial(decode_step, cfg=cfg))
    for t in range(T):
        tb = {"tokens": toks[:, t:t + 1],
              "positions": jnp.full((B, 1), t, jnp.int32)}
        logits, caches = step(params, tb, caches, jnp.int32(t))
    np.testing.assert_allclose(logits, last, atol=2e-5)
    for name in caches:          # one stack over every layer, dense first
        assert caches[name].shape[0] == cfg.n_layers
        np.testing.assert_allclose(caches[name], pre_caches[name],
                                   atol=2e-5)


@pytest.mark.parametrize("held", [0, 8])
def test_param_count_matches_the_initialised_model(held):
    """All 27 layers at published widths (shapes only): the leading dense
    layer, no q-LoRA, and of the routed experts those held."""
    cfg = get_config("deepseek-v2-lite").scaled(experts_held=held)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    n = sum(int(np.prod(a.shape)) for path, a in flat
            if "ln" not in jax.tree_util.keystr(path)
            and "norm" not in jax.tree_util.keystr(path))
    assert cfg.param_count() == n
    assert n == {0: 15_706_357_760, 8: 3_110_862_848}[held]
    expert = 3 * 2048 * 1408 * 26
    assert cfg.active_param_count() == n - expert * ((held or 64) - 6)
