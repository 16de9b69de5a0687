"""The expert layer under a held range, where the combine's transpose is
bounded by the rows in groups (``parallel/expert._combine_held``), against a
dense per-expert reference: output and the gradients in ``x``, the three
banks and the router, with the count of grouped rows set by hand on and
around the chunk edges; rows past the last group filled with NaN reach no
result; every forward program lowers to the plain formulation's text.
One jitted program a (banks, valid) pair (``tests/_sharded.py``'s rule)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lion_tpu.parallel import expert
from distributed_lion_tpu.parallel.expert import (
    ROW_CHUNK,
    moe_dropless_ffn,
    softmax_topk_route,
)

E, D, F, N, K = 8, 32, 16, 2304, 2      # 4,608 picks: two chunks and a part
PICKS = N * K
LANES = 2250                            # real tokens where ``valid`` is given
BANKS = {"quarter": (2, 2), "all": (0, E)}
LEAVES = ("router", "w_gate", "w_up", "w_down")


def _params():
    ks = jax.random.split(jax.random.key(44), 3)
    # the router reads a token's first E features as its logits
    return {"router": jnp.eye(E, D),
            "w_gate": jax.random.normal(ks[0], (E, D, F)) * 0.3,
            "w_up": jax.random.normal(ks[1], (E, D, F)) * 0.3,
            "w_down": jax.random.normal(ks[2], (E, F, D)) * 0.3}


def _tokens(held, lanes: int, count: int):
    """x [N, D] whose first ``lanes`` tokens make exactly ``count`` picks of
    the experts ``held`` names: the logits ride in the first E features, a
    token's two picks get 4 and 3 there and nobody else more than 1."""
    first, banks = held
    inside = list(range(first, first + banks))
    outside = [e for e in range(E) if e not in inside] or inside
    both = min(count // K, lanes) if banks >= K else 0
    one = count - K * both
    assert both + one <= lanes and (one == 0 or outside != inside), count
    rng = np.random.default_rng(count)
    x = rng.normal(size=(N, D)).astype(np.float32)
    x[:, :E] = rng.uniform(0, 1, (N, E))
    for t in range(N):
        if t < both:
            picks = rng.choice(inside, K, replace=False)
        elif t < both + one:
            picks = [rng.choice(inside), rng.choice(outside)]
        else:
            picks = rng.choice(outside, K, replace=False) \
                if outside != inside else rng.choice(inside, K, replace=False)
        x[t, picks[0]], x[t, picks[1]] = 4.0, 3.0
    # held picks anywhere among the real lanes, not in a run at the front
    x[:lanes] = x[rng.permutation(lanes)]
    return jnp.asarray(x)


def _dense(params, x, held, valid):
    """Every held expert over every token, weighted where it was picked."""
    idx, w = softmax_topk_route(x, params["router"], K)
    out = jnp.zeros((N, D), jnp.float32)
    for e in range(held[0], held[0] + held[1]):
        ye = (jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e])
              ) @ params["w_down"][e]
        out = out + ye * jnp.where(idx == e, w, 0).sum(-1, keepdims=True)
    return out if valid is None else jnp.where(valid[:, None], out, 0)


def _held_banks(params, held):
    first, banks = held
    return dict(params, **{n: params[n][first:first + banks]
                           for n in LEAVES[1:]})


@functools.lru_cache(maxsize=None)
def _program(banks: str, masked: bool):
    held = BANKS[banks]

    def both(params, x, valid, probe):
        def mine(params, x):
            y, counters = moe_dropless_ffn(
                _held_banks(params, held), x, top_k=K, scale=1.0, held=held,
                valid=valid if masked else None, return_counters=True)
            return (y * probe).sum(), (y, counters)

        def want(params, x):
            y = _dense(params, x, held, valid if masked else None)
            return (y * probe).sum(), y

        return (jax.value_and_grad(mine, (0, 1), has_aux=True)(params, x),
                jax.value_and_grad(want, (0, 1), has_aux=True)(params, x))

    return jax.jit(both)


def _compare(banks, masked, count):
    held = BANKS[banks]
    lanes = LANES if masked else N
    params = _params()
    x = _tokens(held, lanes, count)
    valid = jnp.arange(N) < lanes
    probe = jax.random.normal(jax.random.key(5), (N, D))
    ((_, (y, counters)), grads), ((_, y_want), grads_want) = _program(
        banks, masked)(params, x, valid, probe)
    assert int(counters["moe_assignments"]) == count
    assert int(counters["moe_routed"]) == lanes * K
    chunks = max(1, -(-count // ROW_CHUNK))
    assert int(counters["moe_rows_moved"]) == min(chunks * ROW_CHUNK, PICKS)
    got = dict(grads[0], y=y, x=grads[1])
    want = dict(grads_want[0], y=y_want, x=grads_want[1])
    for name in want:
        # a bank's gradient sums thousands of rows in another order
        assert np.isfinite(np.asarray(got[name])).all(), name
        np.testing.assert_allclose(
            got[name], want[name], rtol=1e-4, err_msg=name,
            atol=1e-5 * max(1.0, float(jnp.abs(want[name]).max())))


EDGES = [0, ROW_CHUNK - 1, ROW_CHUNK, ROW_CHUNK + 1, 2 * ROW_CHUNK + 300]
CASES = [("quarter", masked, count) for masked in (False, True)
         for count in EDGES + [(LANES if masked else N) * K]] \
    + [("all", False, PICKS), ("all", True, LANES * K)]


@pytest.mark.parametrize("banks,masked,count", CASES)
def test_bounded_rows_give_the_dense_layer_and_its_gradients(banks, masked,
                                                             count):
    """No pick held, a quarter of the banks, all of them with ``held``
    given; ``valid`` on and off; grouped rows at 0, one under a chunk's edge,
    on it, one over it, inside the last (part) chunk, and every pick."""
    _compare(banks, masked, count)


def test_rows_past_the_last_group_are_never_read(monkeypatch):
    """What the bounded moves do not write and what a grouped kernel leaves
    undefined (its rows past the last group: of ``y``, of ``h``, of the
    cotangents) is NaN here, and no NaN reaches the output or a gradient."""
    product = expert._grouped_product

    def undefined_past_groups(lhs, rhs, group_sizes, tail):
        out = product(lhs, rhs, group_sizes, tail)
        grouped = jnp.arange(lhs.shape[0]) < group_sizes.sum()
        return jnp.where(grouped[:, None], out, jnp.nan)

    monkeypatch.setattr(expert, "_grouped_product", undefined_past_groups)
    _program.cache_clear()
    try:
        _compare("quarter", True, ROW_CHUNK + 1)
    finally:
        _program.cache_clear()


# ------------------------------------------------------- the plain program
def _plain_layer(params, x, top_k, valid, held):
    """The layer as it was before a call could be bounded: every pick's row
    gathered, sorted, gathered back, masked and summed."""
    n, d = x.shape
    groups = params["router"].shape[0] if held is None else held[1]
    idx, w = softmax_topk_route(x, params["router"], top_k)
    flat = idx.reshape(-1)
    if held is not None:
        local = flat - held[0]
        flat = jnp.where((local >= 0) & (local < groups), local, groups)
    if valid is not None:
        flat = jnp.where(jnp.repeat(valid, top_k), flat, groups)
    order = jnp.argsort(flat)
    ends = jnp.searchsorted(flat[order], jnp.arange(groups + 1))
    sizes = jnp.diff(ends).astype(jnp.int32)
    rows = x[order // top_k]
    tail = held is not None
    h = expert._swiglu_limited(
        expert.grouped_matmul(rows, params["w_gate"], sizes, tail),
        expert.grouped_matmul(rows, params["w_up"], sizes, tail), 0.0)
    y = expert.grouped_matmul(h, params["w_down"], sizes, tail)
    y = y[jnp.argsort(order)].reshape(n, top_k, d)
    if held is not None:
        y = jnp.where((flat < groups).reshape(n, top_k, 1), y, 0)
    elif valid is not None:
        y = jnp.where(valid[:, None, None], y, 0)
    out = jnp.einsum("nkd,nk->nd", y.astype(jnp.float32), w).astype(x.dtype)
    if valid is not None:
        out = jnp.where(valid[:, None], out, 0)
    lanes = n if valid is None else valid.sum()
    return out, {"moe_assignments": sizes.sum(),
                 "moe_experts_hit": (sizes > 0).sum().astype(jnp.int32),
                 "moe_load_max": sizes.max(),
                 "moe_routed": jnp.asarray(lanes * top_k, jnp.int32)}


@pytest.mark.parametrize("tokens,held,masked", [
    (N, None, False),                   # every expert held: cells 5 and 9
    (N, None, True),                    # ... with pad lanes
    (ROW_CHUNK // K - 1, (2, 2), False),     # a decode tick: under one chunk
    (ROW_CHUNK // K - 1, (2, 2), True),
    (ROW_CHUNK // K, (2, 2), True),     # a prefill under a held range
])
def test_forward_programs_lower_to_the_plain_text(tokens, held, masked):
    """Only the combine's transpose is bounded: every forward program (the
    serving cells') is the plain formulation's text, a call of a chunk of
    picks or more under a held range too; that call's gradient holds the
    loop, a call under one chunk or with every expert held does not."""
    params = _params() if held is None else _held_banks(_params(), held)
    x = jnp.zeros((tokens, D))
    valid = jnp.arange(tokens) < tokens - 3 if masked else None

    def mine(p, x, valid):
        return moe_dropless_ffn(p, x, top_k=K, scale=1.0, valid=valid,
                                held=held, return_counters=True)

    def layer(ffn):
        def program(params, x, valid):
            y, counters = ffn(params, x, valid)
            return y, {name: counters[name] for name in (
                "moe_assignments", "moe_experts_hit", "moe_load_max",
                "moe_routed")}
        return jax.jit(program).lower(params, x, valid).as_text()

    assert layer(mine) == layer(
        lambda p, x, valid: _plain_layer(p, x, K, valid, held))
    grad = jax.jit(jax.grad(lambda p, x, valid: mine(p, x, valid)[0].sum())
                   ).lower(params, x, valid).as_text()
    bounded = held is not None and tokens * K >= ROW_CHUNK
    assert ("dynamic_update_slice" in grad) == bounded
