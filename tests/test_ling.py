"""Ling 3.0 flash (models/ling) against its plain reference at TINY on the
CPU in float32: the gated delta rule's three forms (token-by-token scan,
chunks, the step kernel), prefill then decode through slot-indexed states
and latent pages, the reset a slot gets at admission, group-limited routing
and the held share, what the engine refuses, and the configuration.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import ling_3_flash as family  # noqa: E402
from distributed_lion_tpu.models import joyai, laguna, ling  # noqa: E402
from distributed_lion_tpu.models.ling import (  # noqa: E402
    LingConfig,
    ling_decode_paged,
)
from distributed_lion_tpu.ops import kda, pallas_kda  # noqa: E402
from distributed_lion_tpu.parallel import expert  # noqa: E402
from distributed_lion_tpu.serve.engine import (  # noqa: E402
    Request,
    ServeConfig,
    ServeModel,
    ServingEngine,
)
from distributed_lion_tpu.serve.kv_cache import (  # noqa: E402
    BlockTables,
    init_page_leaves,
)

ref = family.reference
TINY = family.TINY               # experts 0-7 (groups 0, 1) of 16 held
WHOLE = dict(TINY, num_experts=16, reduced=[], published={})
TOL = 1e-4
BLOCK, PER_SEQ = 8, 8            # rows of up to 64 tokens


def build(body):
    weights = ref.init_weights(ref.seed_key(2 ** 31 + 32), body, jnp.float32)
    cfg = LingConfig.from_hf(body, param_dtype=jnp.float32,
                             compute_dtype=jnp.float32)
    return weights, family.to_program(weights), cfg


@pytest.fixture(scope="module")
def model():
    """(reference weights, program params, LingConfig) at TINY, float32:
    the same values in both layouts."""
    return build(TINY)


def engine_of(model, **kw):
    _, params, cfg = model
    base = dict(max_seqs=3, block_size=BLOCK, max_blocks_per_seq=PER_SEQ,
                prefill_cap_tokens=64, moe_stats=True)
    base.update(kw)
    return ServingEngine(ServeModel.for_ling(params, cfg),
                         ServeConfig(**base))


def requests(first_id=0):
    """Prompts that fill their bucket (16, 32) and that do not (11, 21, 12),
    more requests than slots; two buckets in all (each is a compile)."""
    rng = np.random.default_rng(32)
    return [Request(req_id=first_id + i, tokens=rng.integers(0, 256, n).tolist(),
                    max_new_tokens=m, seed=0)
            for i, (n, m) in enumerate([(16, 6), (11, 7), (32, 5), (21, 6),
                                        (12, 4)])]


@pytest.fixture(scope="module")
def batched(model):
    """(engine, its first run's completions, the same requests run AGAIN
    through the engine as the first run left it)."""
    eng = engine_of(model)
    first = eng.run(requests(), arrivals={3: 2, 4: 4})
    return eng, first, eng.run(requests(10))


def pool(cfg, n_seq, model_):
    pages = init_page_leaves(
        cfg.n_layer, n_seq * PER_SEQ, BLOCK, {"kv": (1, cfg.latent_dim)},
        jnp.float32, state=(cfg.kda_layers, n_seq, model_.state_leaves))
    tables = jnp.arange(n_seq * PER_SEQ, dtype=jnp.int32)[::-1].reshape(
        n_seq, PER_SEQ)
    return pages, tables


# ------------------------------------------------- (b) the rule's three forms
def rule_inputs(T, gate, B=2, H=3, dk=16, dv=16, seed=1):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    g = {"bound": jnp.full((B, T, H, dk), -5.0),
         "zero": jnp.full((B, T, H, dk), -1e-4),
         "mixed": -5 * jax.nn.sigmoid(
             jax.random.normal(ks[3], (B, T, H, dk)) * 2 - 3)}[gate]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (B, H, dk, dv))


@jax.jit
def stepped(q, k, v, g, beta, state):
    def one(S, x):
        o, S = kda.kda_step_xla(S, *x)
        return S, o
    S, o = jax.lax.scan(one, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


chunked = jax.jit(kda.kda_chunked)


@pytest.mark.parametrize("gate", ["bound", "zero", "mixed"])
@pytest.mark.parametrize("T", [100, 8])
def test_chunked_form_is_the_step_repeated(gate, T):
    """Every gate at the bound -5 (64 steps: 320 nats, past float32's
    exponent), near 0, and mixed; a length that is no multiple of the chunk
    and one shorter than a sub-block; from a state that is not zero."""
    args = rule_inputs(T, gate)
    o, S = chunked(*args)
    o2, S2 = stepped(*args)
    assert bool(jnp.isfinite(o).all())
    assert float(jnp.abs(o - o2).max()) < 2e-6
    assert float(jnp.abs(S - S2).max()) < 1e-5


def test_the_form_that_divides_by_the_decay_overflows_at_the_bound():
    """What the chunked form must not do: ``k_j exp(-G_j)`` at the gate's
    bound is no float32 by the end of a chunk."""
    G = jnp.cumsum(jnp.full((64,), -5.0))
    assert not bool(jnp.isfinite(jnp.exp(-G)).all())
    assert bool(jnp.isfinite(jnp.exp(G[:, None] - G[None, :])[
        jnp.tril_indices(64)]).all())


def test_chunked_form_on_repeated_keys():
    """A prompt that repeats a token: identical keys, no decay, full write
    strength, where the powers of ``A`` grow like binomials."""
    q, k, v, g, beta, S = rule_inputs(100, "zero")
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.ones_like(beta)
    o, S1 = chunked(q, k, v, g, beta, S)
    o2, S2 = stepped(q, k, v, g, beta, S)
    assert float(jnp.abs(o - o2).max()) < 1e-5
    assert float(jnp.abs(S1 - S2).max()) < 1e-4


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0, 0], [0, 0, 1, 0, 1, 0],
                                  [0] * 6, [1] * 6],
                         ids=["holes", "leading_dead", "all_dead", "all_live"])
def test_step_kernel_is_the_plain_step_and_skips_dead_slots(live):
    """``kda_step`` (interpret mode) at whole lane tiles, dead slots among
    the live: their states come back bit for bit, their outputs zero."""
    B, H, dk, dv = 6, 32, 16, 128
    q, k, v, g, beta, S = rule_inputs(1, "mixed", B=B, H=H, dk=dk, dv=dv)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    live = jnp.asarray(live, bool)
    assert pallas_kda.kernel_takes(S.shape, S.dtype)
    o1, S1 = jax.jit(kda.kda_step_xla)(S, q, k, v, g, beta, live)
    o2, S2 = pallas_kda.kda_step(S, q, k, v, g, beta, live, interpret=True)
    assert float(jnp.abs(o1 - o2).max()) < 1e-6
    assert float(jnp.abs(S1 - S2).max()) < 1e-5
    assert bool((S2[~live] == S[~live]).all())
    assert bool((o2[~live] == 0).all())
    src = np.asarray(pallas_kda.source_slots(live))
    if live.any():     # a dead slot names the live slot before it, else
        # the first live one
        assert all(bool(live[s]) for s in src)
        assert all(src[b] == b for b in range(B) if live[b])
        assert (np.diff(src) >= 0).all()


def test_kernel_is_refused_off_whole_tiles():
    assert not pallas_kda.kernel_takes((4, 4, 16, 16), jnp.float32)
    assert not pallas_kda.kernel_takes((4, 32, 128, 128), jnp.bfloat16)
    assert pallas_kda.kernel_takes((128, 32, 128, 128), jnp.float32)
    assert not pallas_kda.chunk_kernel_takes((1, 4, 16, 16), jnp.float32)
    assert not pallas_kda.chunk_kernel_takes((1, 32, 128, 128), jnp.bfloat16)
    assert pallas_kda.chunk_kernel_takes((1, 32, 128, 128), jnp.float32)


@pytest.mark.parametrize("gate", ["bound", "zero", "mixed", "repeated"])
def test_chunk_kernel_is_the_step_repeated(gate):
    """``kda_chunk`` (interpret mode) at whole lane tiles over 300 positions
    (two chunks and a part of one), from a state that is not zero: the
    scan's outputs and state to what three bfloat16 passes leave, with every
    gate at the bound -5 (a chunk of 128 sums to -640), near 0, mixed, and
    on one key repeated at full write strength."""
    q, k, v, g, beta, S = rule_inputs(
        300, "zero" if gate == "repeated" else gate, B=1, H=2, dk=128, dv=128)
    if gate == "repeated":
        k, beta = jnp.broadcast_to(k[:, :1], k.shape), jnp.ones_like(beta)
    o, S1 = pallas_kda.kda_chunk(q, k, v, g, beta, S, interpret=True)
    o2, S2 = stepped(q, k, v, g, beta, S)
    assert bool(jnp.isfinite(o).all())
    assert float(jnp.abs(o - o2).max()) < 1e-5
    assert float(jnp.abs(S1 - S2).max()) < 1e-4
    if gate == "mixed":        # inert positions leave the state where it was
        idle = jnp.arange(300) >= 200
        g, beta = jnp.where(idle[None, :, None, None], 0.0, g), \
            jnp.where(idle[None, :, None], 0.0, beta)
        S3 = pallas_kda.kda_chunk(q, k, v, g, beta, S, interpret=True)[1]
        S4 = stepped(*(x[:, :200] for x in (q, k, v, g, beta)), S)[1]
        assert float(jnp.abs(S3 - S4).max()) < 1e-4


def test_chunked_dispatches_to_the_kernel_on_a_tpu(monkeypatch):
    """``ops/kda.kda_chunked`` with the TPU's choice at whole lane tiles is
    the kernel; off them, and on any other backend, the XLA form."""
    calls = []
    monkeypatch.setattr(pallas_kda, "kda_chunk",
                        lambda *a: calls.append(a) or (a[2], a[5]))
    whole = rule_inputs(8, "mixed", B=1, H=1, dk=128, dv=128)

    def trace(args):       # a new function a call: no trace is found again
        jax.eval_shape(lambda *a: kda.kda_chunked(*a), *args)
        return len(calls)

    assert trace(whole) == 0                               # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trace(whole) == 1
    assert trace(rule_inputs(8, "mixed")) == 1             # 16 lanes


# ------------------------------------- (a) prefill, then decode, by reference
@pytest.fixture(scope="module")
def hooks(model):
    """The model hook jitted at the two shapes the tests below share: a
    prefill of 3 rows in a bucket of 32, and the decode tick."""
    _, params, cfg = model

    @jax.jit
    def prefill(toks, pages, tables, slots, valid):
        return ling_decode_paged(params, toks, cfg, pages, tables, slots,
                                 jnp.zeros((3,), jnp.int32), valid)

    @jax.jit
    def step(toks, pages, tables, pos):
        return ling_decode_paged(params, toks, cfg, pages, tables, None, pos,
                                 jnp.ones((3, 1), bool))

    return prefill, step


def test_prefill_then_decode_through_state_and_pages(model, hooks):
    """Rows whose prompts fill their bucket (32) and do not (21, 3: shorter
    than the convolution), then one token a step: every logit against the
    reference's full forward pass (the chunked form in the prefill, the
    step in the decode, the reference's own scan). The largest gap at TINY
    in float32 is at rounding level (2.4e-6 measured, PR 32); ``TOL`` is
    1e-4. Then the fault the mask is there for: the same prefill run OVER
    the padding leaves another state and other tails."""
    weights, params, cfg = model
    prefill, step = hooks
    rows = np.random.default_rng(2).integers(0, 256, (3, 48)).astype(np.int32)
    plens = np.asarray([32, 21, 3])
    want = jax.jit(lambda r: ref.forward(weights, r, TINY))(rows)
    fresh, tables = pool(cfg, 3, ServeModel.for_ling(params, cfg))
    slots = jnp.asarray([2, 0, 1])          # a state is found by the slot
    valid = jnp.arange(32)[None, :] < plens[:, None]
    logits, pages = prefill(rows[:, :32], fresh, tables, slots, valid)
    worst = max(float(jnp.abs(logits[i, :n] - want[i, :n]).max())
                for i, n in enumerate(plens))
    # decode rows ARE slots: put each row's state where its row is
    pages = [{k: (v[slots] if k in ("state", "conv") else v)
              for k, v in c.items()} for c in pages]
    for j in range(12):
        at = plens + j
        toks = rows[np.arange(3), at][:, None]
        logits, pages = step(toks, pages, tables, jnp.asarray(at, jnp.int32))
        worst = max(worst, float(jnp.abs(
            logits[:, 0] - want[np.arange(3), at]).max()))
    assert worst < TOL, worst
    # positions past a row's length neither decay nor write
    _, masked = prefill(rows[:, :32], fresh, tables, slots, valid)
    _, over = prefill(rows[:, :32], fresh, tables, slots,
                      jnp.ones((3, 32), bool))
    for layer in cfg.kda_layers:
        for leaf in ("state", "conv"):
            a, b = masked[layer][leaf], over[layer][leaf]
            assert bool(jnp.array_equal(a[2], b[2]))     # the row of 32
            assert float(jnp.abs(a[0] - b[0]).max()) > 1e-3, (layer, leaf)


def test_step_dispatches_to_the_kernel_on_a_tpu(monkeypatch):
    """``ops/kda.kda_step`` with the TPU's choice (the kernel in interpret
    mode) at whole lane tiles, a dead slot among the live, two steps on:
    the plain step's outputs and states."""
    _kernel_path_steps(monkeypatch)


def _kernel_path_steps(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pallas_kda, "kda_step", functools.partial(
        pallas_kda.kda_step, interpret=True))
    B, H, d = 3, 32, 128
    q, k, v, g, beta, S = rule_inputs(2, "mixed", B=B, H=H, dk=d, dv=d)
    live = jnp.asarray([True, False, True])
    step = jax.jit(kda.kda_step_xla)
    for t in range(2):
        args = tuple(x[:, t] for x in (q, k, v, g, beta)) + (live,)
        o1, S1 = step(S, *args)
        o2, S = kda.kda_step(S, *args)           # dispatches to the kernel
        assert float(jnp.abs(o1 - o2).max()) < 1e-5
        assert float(jnp.abs(S1 - S).max()) < 1e-5


def test_engine_tokens_are_the_references_first_choices(model, batched):
    weights = model[0]
    _, out, _ = batched
    rows = np.zeros((5, 64), np.int32)
    for req in requests():
        assert out[req.req_id].reason == "length"
        assert len(out[req.req_id].tokens) == req.max_new_tokens
        seq = list(req.tokens) + out[req.req_id].tokens
        rows[req.req_id, :len(seq)] = seq
    first = np.asarray(jax.jit(
        lambda r: ref.forward(weights, r, TINY).argmax(-1))(rows))
    for req in requests():
        n, m = len(req.tokens), req.max_new_tokens
        assert first[req.req_id, n - 1:n + m - 1].tolist() \
            == out[req.req_id].tokens, req.req_id


# -------------------------------------------------------- (c) the reset
def test_a_slot_admitted_twice_reads_nothing_of_its_last_tenant(model,
                                                                batched):
    """The same five requests through the engine as its first run left it
    (every slot's state and tails written, by other prompts than the ones
    that now take them): the tokens a fresh engine gave. The engine counts a
    reset an admission."""
    eng, first, again = batched
    assert all(bool(eng.pages[i]["state"].any()) for i in model[2].kda_layers)
    for req in requests():
        assert again[req.req_id + 10].tokens == first[req.req_id].tokens
    assert eng.stats["state_resets"] == eng.stats["prefill_dispatches"] == 10


def test_state_leaves_stand_beside_the_pages(model, batched):
    eng = batched[0]
    cfg, st = model[2], eng.stats
    assert cfg.kda_layers == (0, 2) and cfg.mla_layers == (1,)
    shapes = [{k: v.shape for k, v in layer.items()} for layer in eng.pages]
    state = {"state": (3, 4, 16, 16), "conv": (3, 3, 192)}
    assert shapes == [state, {"kv": (24, 8, 1, 128)}, state]
    assert eng.pages[0]["state"].dtype == jnp.float32
    # admission and growth count the latent pages only: everything back
    assert eng.tables.free_blocks == eng.tables.num_blocks == 24
    assert st["state_bytes"] == 2 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert st["state_rows_stepped"] == st["decode_tokens"] * 2
    # token conservation through the engine: picks made, and rows here
    layers = cfg.n_layer - cfg.first_dense
    assert st["moe_routed"] == st["decode_tokens"] * cfg.top_k * layers
    assert st["moe_prefill_routed"] == (st["prefill_tokens"] * cfg.top_k
                                        * layers)
    assert 0.2 < st["moe_assignments"] / st["moe_routed"] < 0.8
    assert st["moe_experts_hit"] <= st["decode_ticks"] * layers * cfg.banks


def test_state_leaves_are_not_counted_against_the_pool():
    """The allocator sees pages alone: a family with state leaves admits
    what its pages allow, whatever the state holds."""
    leaves = init_page_leaves(
        3, 6, 8, {"kv": (1, 40)}, jnp.float32,
        state=((0, 2), 5, {"state": ((2, 4, 4), jnp.float32)}))
    assert [sorted(x) for x in leaves] == [["state"], ["kv"], ["state"]]
    assert leaves[0]["state"].shape == (5, 2, 4, 4)
    assert leaves[1]["kv"].shape == (6, 8, 1, 128)
    assert BlockTables(6, 8, 5, 4).free_blocks == 6


# --------------------------------------------- (d) groups and the held share
@pytest.fixture(scope="module")
def whole():
    return build(WHOLE)


# the dropless layer and the reference's, compiled: one program a call
dropless_ffn = jax.jit(expert.moe_dropless_ffn, static_argnames=(
    "top_k", "scale", "return_counters", "held", "route_groups", "limits"))


def ref_experts(x, layer, body):
    return jax.jit(lambda x, layer: ref._experts(x[None], layer, body, 1,
                                                 None)[0])(x, layer)


def test_the_four_shares_sum_to_the_whole_layer(whole):
    """Experts 0-3, 4-7, 8-11, 12-15 held in turn (a routing group each),
    group-limited routing on, the shared expert counted once: the uncut
    reference's layer."""
    weights, params, cfg = whole
    layer, moe = weights["layers"][1], params["blocks"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((24, 64)),
                    jnp.float32)
    want = ref_experts(x, layer, WHOLE)
    kw = dict(top_k=cfg.top_k, scale=cfg.routed_scale,
              route_groups=(cfg.n_group, cfg.topk_group))
    parts, rows = [], 0
    for first in range(0, 16, 4):
        held = dict(moe, **{k: moe[k][first:first + 4]
                            for k in ("w_gate", "w_up", "w_down")})
        y, st = dropless_ffn(held, x, held=(first, 4), return_counters=True,
                             **kw)
        parts.append(y)
        rows += int(st["moe_assignments"])
        assert int(st["moe_routed"]) == 24 * cfg.top_k
    shared = laguna._mlp(x, moe["shared"])
    assert rows == 24 * cfg.top_k           # every pick is held somewhere
    assert float(jnp.abs(sum(parts) - 3 * shared - want).max()) < 1e-5
    assert float(jnp.abs(parts[0] - want).max()) > 1e-3
    # all held: the layer as one
    y = dropless_ffn(moe, x, **kw)
    assert float(jnp.abs(y - want).max()) < 1e-5


def test_routing_without_groups_is_another_layer(whole):
    """The group limit binds: some token's best 2 of 16 lie in a group that
    does not stay, and the layer without the limit differs."""
    weights, params, cfg = whole
    layer, moe = weights["layers"][1], params["blocks"][1]["moe"]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((24, 64)),
                    jnp.float32)
    limited, _ = expert.sigmoid_topk_route(
        x, moe["router"], moe["bias"], 2, 2.5, (4, 2))
    free, _ = expert.sigmoid_topk_route(x, moe["router"], moe["bias"], 2, 2.5)
    want, _ = ref.route(x, layer, WHOLE)
    assert np.array_equal(np.sort(limited, -1), np.sort(want, -1))
    assert not np.array_equal(np.sort(limited, -1), np.sort(free, -1))
    # a token's picks lie in at most topk_group groups
    assert all(len(set(row // 4)) <= 2 for row in np.asarray(limited))


def test_group_scores_by_hand():
    """4 experts in 2 groups, 1 group stays: the group with the larger sum
    of its two best wins, though the single best expert is in the other."""
    router = jnp.eye(4)
    x = jnp.asarray([[3.0, -9.0, 2.0, 2.0]])      # scores s0 > s2 = s3 > s1
    idx, w = expert.sigmoid_topk_route(x, router, jnp.zeros(4), 1, 1.0,
                                       (2, 1))
    assert idx.tolist() == [[2]] and w.tolist() == [[1.0]]
    idx, _ = expert.sigmoid_topk_route(x, router, jnp.zeros(4), 1, 1.0)
    assert idx.tolist() == [[0]]


def test_swiglu_limits_clamp_where_over_zero(whole):
    weights, params, cfg = whole
    layer, moe = weights["layers"][1], params["blocks"][1]["moe"]
    x = 4 * jnp.asarray(np.random.default_rng(6).standard_normal((8, 64)),
                        jnp.float32)
    limited = dict(WHOLE, expert_swiglu_limit_list=[0, 0.05, 0],
                   share_expert_swiglu_limit_list=[0, 0.02, 0])
    want = ref_experts(x, layer, limited)
    plain = ref_experts(x, layer, WHOLE)
    got = dropless_ffn(
        moe, x, top_k=2, scale=2.5, route_groups=(4, 2),
        limits=LingConfig.from_hf(limited).limits(1))
    assert LingConfig.from_hf(limited).limits(1) == (0.05, 0.02)
    assert LingConfig.from_hf(limited).limits(2) == (0.0, 0.0)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(plain - want).max()) > 1e-3


# ------------------------------ (e) the families that have no groups, as were
def test_one_group_leaves_the_other_families_programs_as_they_were():
    """``n_group`` 1 adds nothing: the router's jaxpr with the default is
    the one with no group argument at all; JoyAI and Laguna pass none."""
    x, r, b = jnp.ones((5, 8)), jnp.ones((6, 8)), jnp.zeros(6)
    plain = jax.make_jaxpr(lambda x: expert.sigmoid_topk_route(
        x, r, b, 2, 2.5))(x)
    one = jax.make_jaxpr(lambda x: expert.sigmoid_topk_route(
        x, r, b, 2, 2.5, (1, 1)))(x)
    grouped = jax.make_jaxpr(lambda x: expert.sigmoid_topk_route(
        x, r, b, 2, 2.5, (2, 1)))(x)
    assert str(plain) == str(one) != str(grouped)
    import inspect
    for mod in (joyai, laguna):
        assert "route_groups" not in inspect.getsource(mod)


def route_as_it_was(x, router, bias, top_k, scale, groups=(1, 1)):
    """``parallel/expert.sigmoid_topk_route`` of the parent commit (0bebea6),
    word for word."""
    from jax import lax
    assert groups == (1, 1)
    with jax.named_scope("moe/route"):
        s = jax.nn.sigmoid(jnp.einsum(
            "nd,ed->ne", x.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(s, idx, axis=1)
        return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * scale


@pytest.mark.parametrize("name", ["joyai", "laguna"])
def test_other_families_logits_are_bit_identical(name, monkeypatch):
    """JoyAI's and Laguna's prefill programs as lowered with the router as
    it is now and with the parent commit's router in its place: the same
    program text, so the same bits."""
    toks = jnp.arange(16, dtype=jnp.int32)[None] * 7 % 256
    tables = jnp.arange(8, dtype=jnp.int32)[None]
    zero = jnp.zeros((1,), jnp.int32)
    if name == "joyai":
        cfg = joyai.JoyAIConfig.tiny(param_dtype=jnp.float32,
                                     compute_dtype=jnp.float32)
        params = joyai.joyai_init(jax.random.key(0), cfg)
        pages = init_page_leaves(cfg.n_layer, 8, 8,
                                 {"kv": (1, cfg.latent_dim)}, jnp.float32)

        def run(params):
            return joyai.joyai_decode_paged(params, toks, cfg, pages, tables,
                                            zero)[0]
    else:
        from distributed_lion_tpu.ops.attention import ring_pages

        cfg = laguna.LagunaConfig.tiny(param_dtype=jnp.float32,
                                       compute_dtype=jnp.float32)
        params = laguna.laguna_init(jax.random.key(0), cfg)
        kv = (cfg.n_kv_head, cfg.head_dim)
        pages = init_page_leaves(
            cfg.n_layer, 8, 8, {"k": kv, "v": kv}, jnp.float32,
            ring=(cfg.window_layers, ring_pages(cfg.window, 8)))

        def run(params):
            return laguna.laguna_decode_paged(params, toks, cfg, pages,
                                              tables, zero, zero)[0]

    text = jax.jit(run).lower(params).as_text()
    monkeypatch.setattr(expert, "sigmoid_topk_route", route_as_it_was)
    assert jax.jit(run).lower(params).as_text() == text
    assert "moe/groups" not in text and "top_k" in text


# ------------------------------------------------------- (f) the refusals
@pytest.mark.parametrize("kw,flag,sentence", [
    ({"prefix_cache": True}, "--prefix_cache", "no pages to share"),
    ({"speculate": "ngram:2"}, "--speculate", "cannot be rolled back"),
    ({"tp": 2}, "--serve_tp", "no sharding spec"),
    ({"ep": 2}, "--serve_ep", "exchange between ranges is not built")])
def test_engine_refuses_what_a_state_cannot_serve(model, kw, flag, sentence):
    with pytest.raises(ValueError,
                       match=f"recurrent state.*{flag}.*{sentence}"):
        engine_of(model, **kw)


def test_engine_refuses_to_quantize_this_family(model):
    with pytest.raises(ValueError, match="serves on one device"):
        engine_of(model, quant="nf4")


# -------------------------------------------------------------------- CLI
def test_config_from_the_published_keys():
    path = os.path.join(ROOT, "benchmark", "configs",
                        "ling-3.0-flash-vl.json")
    cfg = LingConfig.named(path)
    assert (cfg.n_layer, cfg.n_experts, cfg.top_k) == (7, 512, 8)
    assert (cfg.n_group, cfg.topk_group) == (8, 4)
    assert cfg.held == (0, 128) and cfg.banks == 128
    assert cfg.kda_layers == (0, 1, 2, 3, 4, 6) and cfg.mla_layers == (5,)
    assert LingConfig().kda_layers[:6] == (0, 1, 2, 3, 4, 6)
    assert len(LingConfig().mla_layers) == 7
    assert cfg.first_dense == 1 and cfg.group == 6
    assert (cfg.vocab_size, cfg.d_model, cfg.d_ff) == (39296, 2560, 6144)
    assert (cfg.n_head, cfg.head_dim, cfg.conv_channels) == (32, 128, 12288)
    assert (cfg.moe_d_ff, cfg.shared_d_ff) == (768, 768)
    assert (cfg.latent_dim, cfg.gate_floor, cfg.rope_theta) == (576, -5.0, 6e6)
    assert all(cfg.limits(i) == (0.0, 0.0) for i in range(7))
    m = ServeModel.for_ling(None, cfg)
    assert m.state_layers == cfg.kda_layers and m.page_leaves == {
        "kv": (1, 576)}
    assert m.state_leaves["state"] == ((32, 128, 128), jnp.float32)
    assert m.state_leaves["conv"] == ((3, 12288), jnp.bfloat16)
    with pytest.raises(ValueError, match="not implemented"):
        LingConfig.from_hf(dict(TINY, q_lora_rank=1536))
    with pytest.raises(ValueError, match="not implemented"):
        LingConfig.from_hf(dict(TINY, kda_safe_gate=False))
    with pytest.raises(ValueError, match="unknown ling model_name"):
        LingConfig.named("ling-mini")


def test_tiny_is_the_families_tiny():
    cfg = LingConfig.from_hf(TINY)
    assert dataclasses.replace(LingConfig.tiny(), held=(0, 8)) \
        == dataclasses.replace(cfg, expert_limits=(), shared_limits=())


def test_run_serve_names_the_family():
    from distributed_lion_tpu.cli import run_generate, run_serve

    gen = run_generate.GenerateArguments(model_family="ling",
                                         model_name="tiny", temperature=0.0,
                                         max_new_tokens=4)
    serve = run_serve.ServeArguments(max_seqs=2, block_size=8,
                                     max_blocks_per_seq=4)
    tok, engine = run_serve.build_engine(gen, serve)
    assert engine.model.family == "ling"
    assert engine.model.state_layers == (0, 2)
    out = engine.run([Request(req_id="a", tokens=tok.encode("The answer",
                                                             add_bos=False))])
    assert out["a"].reason == "length" and len(out["a"].tokens) == 4
    with pytest.raises(ValueError, match="serve it with run_serve"):
        run_generate.main(["--model_family", "ling", "--model_name", "tiny"])
