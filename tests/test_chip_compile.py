"""Compile the main path's kernels and programs for a DESCRIBED TPU v5e — the
chip's own compiler, no chip attached (on-chip-measurement guide §2.3).

Interpret-mode tests cannot see what Mosaic or the TPU backend refuses: the
vote-health stats kernel passed every interpret-mode test for 19 PRs and was
refused on its first real compile (an int8 vector compare), and the 1-bit
codec compiled in time linear in the ballot length. These cases pin both at
GPT-2 124M widths, at no chip time.

Everything that touches the topology lives in fixtures (one process may
load libtpu; the file's tests all run in the worker that owns it), the
compiles run in the test's own process, and the persistent compile cache is
off around them (an entry compiled for a described device cannot be read
back without a chip).
"""

import dataclasses
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

N_124M = 124_439_808  # GPT-2 124M flat parameter count


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _named_custom_call(text, name):
    """A Mosaic custom-call instruction ``%<name>[.<n>] = ... custom-call``
    in compiled HLO text."""
    return re.search(r"%%%s(\.\d+)? = [^\n]*custom-call\([^\n]*"
                     r'custom_call_target="tpu_custom_call"' % name, text)


_LAYOUT_OPS = ("copy", "slice", "pad", "concatenate", "reshape", "transpose")


def _big_f32_layout_ops(text, least=2 ** 20):
    """Instructions of compiled HLO text, in any computation (a fusion's
    body included), that copy, slice, pad, concatenate, reshape or transpose
    into a float32 result of ``least`` elements or more: what XLA does to a
    leaf that a Mosaic call could not take where it lay. (The asynchronous
    ``copy-start`` / ``slice-start`` pairs are the compiler moving a buffer
    between memories, not a relayout, and are other opcodes.)"""
    found = []
    for m in re.finditer(r"^\s*(?:ROOT )?%?[\w.\-]+ = f32\[([\d,]+)\]\S* ("
                         + "|".join(_LAYOUT_OPS) + r")\(.*$", text, re.M):
        if np.prod([int(d) for d in m.group(1).split(",")]) >= least:
            found.append(m.group(0).strip()[:200])
    return found


def _compile(fn, *args):
    """Compile for the described chip; returns (text, seconds)."""
    t0 = time.monotonic()
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text, time.monotonic() - t0


# ------------------------------------------------------- pallas_lion kernels
@pytest.mark.parametrize("mom", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", ["ballots", "apply", "stats"])
def test_lion_kernel_compiles_at_124m(one_chip, kernel, mom):
    from distributed_lion_tpu.ops import pallas_lion

    def s(dt):
        return jax.ShapeDtypeStruct((N_124M,), dt, sharding=one_chip)

    if kernel == "ballots":
        fn, args = (lambda g, m: pallas_lion.fused_ballots(g, m, 0.9),
                    (s(mom), s(mom)))
    elif kernel == "apply":
        fn = lambda p, g, m, t: pallas_lion.fused_apply(  # noqa: E731
            p, g, m, t, 1e-4, 0.1, 0.99)
        args = (s("float32"), s(mom), s(mom), s("int32"))
    else:
        # the refused kernel: `ballot_ref[:] > 0` on int8 lowered to an
        # arith.cmpi over vector<8x128x4xi8>, which v5e's Mosaic rejects.
        # (mom only varies the tally dtype the bucket pipeline hands it)
        fn = lambda b, t: pallas_lion.bucket_vote_stats(  # noqa: E731
            b, t, 4, 8)
        args = (s("int8"), s("int8" if mom == "bfloat16" else "int32"))
    text, _ = _compile(fn, *args)
    assert "tpu_custom_call" in text


def test_lion_kernels_compile_on_odd_window(one_chip):
    """The leaf-shaped entries over a window that starts at a later row
    block and ends, off a block and off a tile, with its 50257-row leaf."""
    from distributed_lion_tpu.ops import pallas_lion

    shape, rows, block = (50257, 768), (49664, 50257), 512

    def s(dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def fn(p, g, m):
        b = pallas_lion.leaf_ballots(g, m, 0.9, rows=rows, block=block)
        h, d = pallas_lion.bucket_vote_stats(b.reshape(-1), b.reshape(-1),
                                             1, 8)
        return pallas_lion.leaf_apply(p, g, m, b, 1e-4, 0.1, 0.99,
                                      rows=rows, block=block), h, d

    text = jax.jit(fn, donate_argnums=(0, 2)).lower(
        s("float32"), s("float32"), s("float32")).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    # each Mosaic custom-call's instruction is named after its kernel, which
    # is the name a device trace shows (not ``fn.<n>``)
    for kernel in ("lion_ballot", "lion_stats", "lion_apply"):
        assert _named_custom_call(text, kernel), kernel
    # the window is written into the leaf itself: no copy of it is made
    assert not _big_f32_layout_ops(text)


def test_sign_codec_compile_time_is_flat_in_n(one_chip):
    """pack/unpack at the 124M ballot, ragged tail included. The textbook
    [n/8, 8] formulation measured 10 s (pack) and 20-28 s (unpack) PER
    MILLION coordinates here — every packed wire, the telemetry's packed
    election and the vote guard's packed ballot compile through these."""
    from distributed_lion_tpu.ops.codec import pack_signs, unpack_signs

    n = N_124M + 5
    _, t_pack = _compile(
        pack_signs, jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip))
    _, t_unpack = _compile(
        lambda b: unpack_signs(b, (n,)),
        jax.ShapeDtypeStruct(((n + 7) // 8,), jnp.uint8, sharding=one_chip))
    assert t_pack < 20 and t_unpack < 20, (t_pack, t_unpack)


def test_packed_row_tally_compiles_flat_at_group_aligned_rows(one_chip):
    """The packed wires' [W, m] tally at m = 2^25 — a row length that is a
    whole number of pack groups. Re-tiling the flat bit vector into [W, m]
    compiled in 100 s here (linear in m); the row-at-a-time tally must not."""
    from distributed_lion_tpu.ops.codec import tally_packed_rows

    _, seconds = _compile(
        tally_packed_rows,
        jax.ShapeDtypeStruct((4, 2 ** 22), jnp.uint8, sharding=one_chip))
    assert seconds < 20, seconds


# ------------------------------------------------------------ flash attention
def test_flash_attention_fwd_bwd_compiles(one_chip):
    """The library's kernel at its own tiles, T = 2048, head_dim 128."""
    from distributed_lion_tpu.ops.attention import attention_flash

    x = jax.ShapeDtypeStruct((1, 8, 2048, 128), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        return attention_flash(q, k, v).astype(jnp.float32).sum()

    text, _ = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


@pytest.mark.parametrize("T", [1024, 2048, 4096])
def test_llama_block_takes_the_library_kernel_from_T2048(one_chip, monkeypatch,
                                                         T):
    """Why the library's kernel stays: one ``models/llama`` block of
    Llama's head_dim (128; 4 query heads over 2 kv heads keep the compile
    short), forward and backward, with attention ``auto`` as a TPU backend
    resolves it. From T = 2048 the program holds the library's three
    kernels and no ``[.., T, T]`` buffer of any type; at T = 1024 it holds
    no Pallas call and materialises the float32 scores."""
    from distributed_lion_tpu.models import llama

    cfg = llama.LlamaConfig(vocab_size=256, n_layer=1, n_head=4, n_kv_head=2,
                            d_model=512, d_ff=1024, n_ctx=T, remat=False)
    assert cfg.head_dim == 128 and cfg.attn_impl == "auto"
    p = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda: llama.llama_init(jax.random.key(0), cfg)
                       )["blocks"][0])
    x = jax.ShapeDtypeStruct((2, T, 512), jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        cos, sin = llama.rope_angles(T, cfg.head_dim, cfg.rope_theta)
        return llama._block(x, p, cfg, cos, sin).astype(jnp.float32).sum()

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text, _ = _compile(jax.grad(loss, argnums=(0, 1)), p, x)
    kernels = re.findall(
        r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    scores = set(re.findall(r"\w+\[[\d,]*%d,%d\]" % (T, T), text))
    if T >= 2048:
        assert len(kernels) == 3 and all(
            re.match(r"flash_(attention|mha_bwd_dq|mha_bwd_dkv)", k)
            for k in kernels), kernels
        assert not scores, scores
    else:
        assert not kernels, kernels
        assert any(s.startswith("f32[") for s in scores), scores


@pytest.mark.parametrize("shape,dtype", [
    ((4, 1024, 12, 64), jnp.bfloat16),    # the 124M train step
    ((1, 2048, 32, 128), jnp.bfloat16),   # a head a lane block
    ((1, 8192, 8, 128), jnp.bfloat16),    # the longest T the kernel takes
    ((2, 1024, 12, 64), jnp.float32),     # compute_dtype float32
], ids=["124m", "T2048-hd128", "T8192-hd128", "124m-f32"])
def test_repo_flash_kernels_compile(one_chip, shape, dtype):
    """``ops/pallas_flash_attn`` forward and fused backward, token-major,
    at real widths: two Mosaic calls under the names the benchmark's
    readers find."""
    from distributed_lion_tpu.ops.pallas_flash_attn import (
        flash_qkv, kernel_takes,
    )

    B, T, H, hd = shape
    assert kernel_takes(T, H, hd, dtype)
    x = jax.ShapeDtypeStruct((B, T, 3 * H * hd), dtype, sharding=one_chip)
    text, _ = _compile(
        jax.grad(lambda x: flash_qkv(x, H).astype(jnp.float32).sum()), x)
    for kernel in ("flash_attention_fwd", "flash_mha_bwd"):
        assert _named_custom_call(text, kernel), kernel


# ``benchmark/lib/layer_common.FLASH_KERNELS``, copied: tests outside
# tests/benchmark hold the harness to its string, not to its module
FLASH_KERNELS = r"flash_attention|flash_mha"


RUNGS = ("none", "dots", "full")   # train/remat.RUNGS: what a block saves


def _two_blocks(place, rung="full"):
    """Two GPT-2 124M blocks wrapped as ``rung`` says (``train/remat``:
    plain, checkpoint keeping the matmul outputs, checkpoint keeping
    nothing), forward and backward: ``(loss, blocks)`` with the blocks'
    float32 parameters as shapes under ``place(shape)``."""
    from distributed_lion_tpu.models import gpt2
    from distributed_lion_tpu.train import remat

    cfg = remat.with_rung(gpt2.GPT2Config.gpt2_124m(n_layer=2, dropout=0.0),
                          rung)
    blocks = jax.tree.map(
        place, jax.eval_shape(lambda: gpt2.gpt2_init(jax.random.key(0), cfg)
                              )["blocks"])
    block = gpt2._block_remat_for(cfg) if cfg.remat else gpt2._block

    def loss(blocks, x):
        for p in blocks:
            p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
            x = block(x, p, None, cfg, None, None)
        return x.astype(jnp.float32).sum()

    return loss, blocks


@pytest.fixture(scope="module")
def train_blocks(one_chip):
    """The two blocks at a training cell's microbatch under a rung, compiled
    for the described chip with attention ``auto`` as a TPU backend resolves
    it (this process's backend is the CPU, so the fixture says "tpu" in its
    place while it traces): ``(HLO text, bytes of temporaries)``. One
    compile a microbatch and rung (7 s), shared by the pins below."""
    built = {}

    def compiled(B, rung):
        if (B, rung) not in built:
            loss, blocks = _two_blocks(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), rung)
            x = jax.ShapeDtypeStruct((B, 1024, 768), jnp.bfloat16,
                                     sharding=one_chip)
            real = jax.default_backend
            jax.default_backend = lambda: "tpu"
            try:
                exe = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
                    blocks, x).compile()
            finally:
                jax.default_backend = real
            built[B, rung] = (exe.as_text(),
                              exe.memory_analysis().temp_size_in_bytes)
        return built[B, rung]

    return compiled


@pytest.fixture(scope="module")
def train_blocks_hlo(train_blocks):
    """The HLO text alone, of the rung a cell runs (both cells resolve
    ``auto`` to ``none`` on a v5e) unless a test names another."""
    return lambda B, rung="none": train_blocks(B, rung)[0]


def _instructions(text, opcode):
    """(name, shape, op_name) of every ``opcode`` instruction in compiled
    HLO text, fused computations included."""
    out = []
    for line in text.splitlines():
        m = re.search(r"%(\S+) = (\S+) " + opcode + r"\(", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), op.group(1) if op else ""))
    return out


# (`dots` is the rung neither cell runs; its two compiles are slow-marked to
# keep the tier-1 suite inside its limit)
@pytest.mark.parametrize("rung", [
    "none", pytest.param("dots", marks=pytest.mark.slow), "full"])
@pytest.mark.parametrize("B", [20, 4], ids=["readme-20x8", "vote-4x2"])
def test_train_blocks_hold_the_repo_flash_kernels(train_blocks_hlo, B, rung):
    """A layer's forward and one fused backward, and under ``dots`` and
    ``full`` the checkpoint's second forward, each under a name
    ``FLASH_KERNELS`` matches (``flash_ms.train`` and ``flash_roofline``
    read the device ops by that pattern), and no call of the library's
    kernels. ``none``, what both cells run since PR 31, holds ONE forward a
    layer."""
    text = train_blocks_hlo(B, rung)
    names = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
                       text)
    kinds = [re.sub(r"\.\d+$", "", n) for n in names]
    assert set(kinds) == {"flash_attention_fwd", "flash_mha_bwd"}, names
    assert kinds.count("flash_mha_bwd") == 2, names
    # (under `full` the last block's forward and its recompute are one
    # call: XLA merges the two, which nothing separates in a two-block
    # program; `dots` keeps the projection between them)
    assert kinds.count("flash_attention_fwd") in {
        "none": (2,), "dots": (4,), "full": (3, 4)}[rung], names
    assert all(re.search(FLASH_KERNELS, n) for n in names)


@pytest.mark.parametrize("B", [20, 4], ids=["readme-20x8", "vote-4x2"])
def test_resolver_counts_a_block_as_the_compiler_does(train_blocks, B):
    """``train/remat.block_saved_bytes`` against ``memory_analysis()``: in
    two blocks, `none` holds one block's saved tensors more than `full`
    (which holds the first block's input and the second block recomputed:
    ``predicted_peaks``), so the difference of the temporaries is what a
    block saves over its input. Measured here in PR 31: 8.63 U at 20
    sequences and 7.85 U at 4 (U = B x 1024 x 768 x 2 B) where the count
    says 9.03 U; over 12 layers and the whole step the same compiler reads
    6.51 / 6.17 / 3.13 GB at 20 sequences and 3.25 / 3.18 / 2.38 GB at 4
    where the count says 6.44 / 6.05 / 3.28 and 3.24 / 3.25 / 2.66 (no
    vote). `dots` saves a tenth less than `none` and recomputes one block:
    in two blocks it is not the smaller (its total is held at 20 sequences;
    at 4 the compiler packs two blocks tighter than any count, and only
    "not under-counted" holds)."""
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train import remat

    saved = remat.block_saved_bytes(GPT2Config.gpt2_124m(), B, 1024)
    want = remat.predicted_peaks(saved, 2, 0)
    got = {rung: train_blocks(B, rung)[1] for rung in ("none", "full")}
    gap = want["none"] - want["full"]
    assert abs(got["none"] - got["full"] - gap) <= 0.15 * gap, (got, want)
    assert got["none"] <= 1.15 * want["none"]


@pytest.mark.slow
@pytest.mark.parametrize("B", [20, 4], ids=["readme-20x8", "vote-4x2"])
def test_resolver_counts_the_dots_rung_as_the_compiler_does(train_blocks, B):
    """`dots` in two blocks: eighteen U saved and one block recomputed.
    (``predicted_peaks`` counts the first block's input, which is the
    program's argument here and no temporary.)"""
    from distributed_lion_tpu.models.gpt2 import GPT2Config
    from distributed_lion_tpu.train import remat

    saved = remat.block_saved_bytes(GPT2Config.gpt2_124m(), B, 1024)
    dots = remat.predicted_peaks(saved, 2, 0)["dots"] - saved["full"]
    got = train_blocks(B, "dots")[1]
    if B == 20:
        assert abs(got - dots) <= 0.15 * dots, (got, dots)
    assert got <= 1.15 * dots


@pytest.mark.parametrize("B", [20, 4], ids=["readme-20x8", "vote-4x2"])
def test_train_blocks_broadcast_no_softmax_statistic(train_blocks_hlo, B):
    """What ISSUE 27 found in the library's backward: ``m``, ``l`` and
    ``di`` spread over 128 lanes (``f32[B,12,1024,128]`` x 5) and ``di``
    over ``block_k_major`` (``f32[B,12,1024,1024]``, 1 GB at B = 20) a
    layer. The residual is one float32 a row now: no float32 broadcast of
    a per-row statistic to 128 lanes or more exists, in any layout of it."""
    wide = [(n, shape) for n, shape, _ in
            _instructions(train_blocks_hlo(B), "broadcast")
            if re.match(r"f32\[%d,(12,1024|1024,12),(\d+)\]" % B, shape)
            and int(re.match(r"f32\[[\d,]*,(\d+)\]", shape).group(1)) >= 128]
    assert not wide, wide


@pytest.mark.parametrize("B", [20, 4], ids=["readme-20x8", "vote-4x2"])
def test_train_blocks_copy_no_attention_activation(train_blocks_hlo, B):
    """No head-major copy (``[B,1024,12,64]`` / ``[B,12,1024,64]``: twelve a
    layer before) and no re-laying of the fused projection or its cotangent
    (``[B,1024,2304]`` / ``[B,1024,3,768]``: the projection is born in the
    kernel's layout) under an ``attn`` op_name."""
    acts = r"bf16\[%d,(1024,12,64|12,1024,64|1024,2304|1024,3,768)\]" % B
    bad = [(n, shape, op) for n, shape, op in
           _instructions(train_blocks_hlo(B), "copy")
           + _instructions(train_blocks_hlo(B), "transpose")
           if "/attn/" in op and re.match(acts, shape)]
    assert not bad, bad


# ------------------------------------------------------ paged serving engine
# ------------------------------------------------- the loss head (ops/xent)
# tests/benchmark hold the harness to its string, not to its module
XENT_KERNELS = r"fused_xent"


@pytest.mark.parametrize("rows,heads,d,valid_v", [
    (20 * 1024, 50257, 768, 0), (4 * 1024, 50257, 768, 0),
    (3 * 1023, 50304, 768, 50257), (2 * 8192, 24576, 2304, 0),
    (2 * 8192 + 27, 24576, 2304, 0),
], ids=["readme-20x8", "vote-4x2", "ragged-padded", "share-2x2-8k",
        "share-ragged-short-group"])
def test_fused_xent_kernels_compile(one_chip, rows, heads, d, valid_v):
    """The kernel pair alone at the training cells' rows against their
    heads as they lie (GPT-2's ``[50257, 768]``, the last vocabulary tile a
    partial block; cell 10's ``[24576, 2304]`` at 512 x 512 tiles, four
    groups of eight blocks), at rows that are no multiple of a block under
    a head padded to 50,304 rows, and at cell 10's width with rows that
    leave the last group short (33 blocks in five groups of seven): Mosaic
    takes the tiles, the 31.5 / 37.7 MB float32 ``dh`` scratch, the
    skipped steps and the index maps."""
    from distributed_lion_tpu.ops import pallas_xent

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def grads(h, w, labels, g):
        def loss(h, w):
            nll, _ = pallas_xent.fused_xent(h, w, labels, valid_v)
            return (nll * g).sum()
        return jax.grad(loss, argnums=(0, 1))(h, w)

    text, _ = _compile(grads, s((rows, d), jnp.bfloat16),
                       s((heads, d), jnp.bfloat16),
                       s((rows,), jnp.int32), s((rows,), jnp.float32))
    for kernel in ("fused_xent_fwd", "fused_xent_bwd"):
        assert _named_custom_call(text, kernel), kernel


@pytest.fixture(scope="module")
def train_step_hlo(topo, one_chip):
    """Loss and gradients of the trainer's dense GPT-2 loss
    (``train/loop.gpt2_clm_loss``: what ``Trainer.for_gpt2`` steps at both
    training cells) for a GPT-2 of the published widths and vocabulary cut
    to two layers, at a cell's microbatch, compiled for the described chip
    with every ``auto`` resolved as a TPU backend resolves it."""
    from distributed_lion_tpu.models import gpt2
    from distributed_lion_tpu.train.loop import TrainConfig, gpt2_clm_loss

    mesh = Mesh(np.array(topo.devices[:1]), ("data",))

    texts = {}

    def compiled(B):
        if B not in texts:
            cfg = gpt2.GPT2Config.gpt2_124m(n_layer=2, dropout=0.0)
            params = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=one_chip),
                jax.eval_shape(lambda: gpt2.gpt2_init(jax.random.key(0), cfg)))
            tokens = jax.ShapeDtypeStruct((B, 1024), jnp.int32,
                                          sharding=one_chip)
            loss, _ = gpt2_clm_loss(TrainConfig(), mesh, cfg)
            real = jax.default_backend
            jax.default_backend = lambda: "tpu"
            try:
                texts[B], _ = _compile(
                    jax.value_and_grad(lambda p, t: loss(p, t, None),
                                       has_aux=True), params, tokens)
            finally:
                jax.default_backend = real
        return texts[B]

    return compiled


@pytest.mark.parametrize("B", [20, 4], ids=["readme-20x8", "vote-4x2"])
def test_train_step_holds_the_loss_head_kernels(train_step_hlo, B):
    """One forward and one backward kernel, under names the benchmark's
    pattern matches (``xent_ms.train`` and ``xent_roofline`` read the
    device ops by it)."""
    names = re.findall(r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
                       train_step_hlo(B))
    kinds = [re.sub(r"\.\d+$", "", n) for n in names
             if re.search(XENT_KERNELS, n)]
    assert sorted(kinds) == ["fused_xent_bwd", "fused_xent_fwd"], names


@pytest.mark.parametrize("B", [20, 4], ids=["readme-20x8", "vote-4x2"])
def test_train_step_holds_no_float32_logits(train_step_hlo, B):
    """No float32 buffer of the logits' shape, in any instruction, fused
    computations included: ``[B, 1023 | 1024, 50257]`` (4.11 GB at B = 20,
    two of them alive at the parent's peak) or its rows flattened."""
    text = train_step_hlo(B)
    rows = "|".join(str(B * t) for t in (1023, 1024))
    logits = re.findall(r"f32\[(?:%d,(?:1023|1024)|%s),50\d\d\d\]"
                        % (B, rows), text)
    assert not logits, sorted(set(logits))
    assert not re.search(r"\[[\d,]*50257\]", text), "a vocabulary-minor buffer"


def test_loss_head_compiles_under_the_workers_shard_map(topo, monkeypatch):
    """Cell 4's path: the loss head inside a ``shard_map`` over the four
    chips' ``data`` axis, 4 sequences a worker, the head replicated: each
    worker runs the kernel pair on its own rows, no collective among them,
    no float32 logits on any."""
    from distributed_lion_tpu.ops import xent as xent_ops

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    hidden = jax.ShapeDtypeStruct((16, 1024, 768), jnp.bfloat16,
                                  sharding=split)
    head = jax.ShapeDtypeStruct((50257, 768), jnp.float32, sharding=repl)
    tokens = jax.ShapeDtypeStruct((16, 1024), jnp.int32, sharding=split)

    def worker(h, w, t):
        dh, dw = jax.grad(
            lambda h, w: xent_ops.clm_head_loss(h, w, t, layout="vd")[0],
            argnums=(0, 1))(h, w)
        return dh, dw[None]                     # a worker's own gradient

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = jax.shard_map(worker, mesh=mesh,
                         in_specs=(P("data"), P(), P("data")),
                         out_specs=(P("data"), P("data")), check_vma=False)
    text, _ = _compile(step, hidden, head, tokens)
    assert _named_custom_call(text, "fused_xent_fwd")
    assert _named_custom_call(text, "fused_xent_bwd")
    assert not re.search(r"all-(reduce|gather|to-all)", text)
    assert not re.search(r"f32\[[\d,]*50257\]", text)


@pytest.mark.parametrize("kind", ["decode_tick", "prefill_bucket"])
def test_paged_decode_compiles_with_donated_pool(one_chip, kind):
    """gpt2_decode_paged at 124M widths with the page pool donated — the
    engine turns donation on only off-CPU, so no CPU test ever compiled
    these programs."""
    from distributed_lion_tpu.models.gpt2 import (
        GPT2Config, gpt2_decode_paged, gpt2_init,
    )

    cfg = GPT2Config.gpt2_124m()
    block, per_seq = 16, 64                       # ctx 1024 per sequence
    b, s_len = (32, 1) if kind == "decode_tick" else (1, 512)

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(lambda: gpt2_init(jax.random.key(0), cfg)))
    page = jax.ShapeDtypeStruct((32 * per_seq, block, cfg.n_head,
                                 cfg.head_dim), cfg.compute_dtype,
                                sharding=one_chip)
    pages = [{"k": page, "v": page} for _ in range(cfg.n_layer)]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(params, pages, toks, tables, pos):
        valid = jnp.arange(s_len)[None, :] < jnp.maximum(pos[:, None], 1)
        logits, pages = gpt2_decode_paged(params, toks, cfg, pages, tables,
                                          pos, valid)
        return jnp.argmax(logits[:, -1], -1), pages

    t0 = time.monotonic()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pages, i32(b, s_len), i32(b, per_seq), i32(b)).compile()
    assert time.monotonic() - t0 < 120
    text = compiled.as_text()
    assert "input_output_alias" in text  # pool really aliased
    # the named regions reach the compiled ops' metadata
    for scope in ("embed", "attn", "mlp", "head", "paged_scatter",
                  "paged_gather", "paged_attn"):
        assert re.search(r'op_name="[^"]*/%s/' % scope, text), scope


@pytest.mark.parametrize("B,H,KV,hd", [(32, 25, 25, 64),    # GPT-2 XL
                                       (16, 32, 32, 128),   # Llama 7B
                                       (16, 32, 8, 128)],   # GQA 4:1
                         ids=["gpt2xl", "llama7b", "gqa"])
def test_paged_attn_kernel_compiles_at_real_widths(one_chip, B, H, KV, hd):
    """The decode kernel at the widths it serves, pool at cell 2's size:
    Mosaic takes the page slabs (25 x 64 = 1600 lanes padded to 1664), the
    kernel keeps its name, and the pool is handed to it where it lies."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.ops.pallas_paged_attn import paged_attn
    from distributed_lion_tpu.serve.kv_cache import pool_row_width

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaf = on_chip((2048, 16, 1, pool_row_width(KV, hd)))
    text, secs = _compile(
        lambda q, k, v, t, n: paged_attn(q, k, v, t, n, kv_heads=KV),
        on_chip((B, H, hd)), leaf, leaf, on_chip((B, 64), jnp.int32),
        on_chip((B,), jnp.int32))
    assert secs < 60
    assert _named_custom_call(text, "paged_attn")
    assert not pool_leaf_copies(text, leaf)


@pytest.mark.parametrize("kind", ["decode_tick", "prefill_bucket", "cow"])
def test_serving_programs_read_the_pool_in_place(one_chip, kind, monkeypatch):
    """The three serving programs over the pool as the engine lays it out
    (serve/kv_cache.init_pages), donated, at 124M widths: none copies a
    pool leaf, and the decode tick holds the ``paged_attn`` kernel where
    the prefill window keeps the gather. The kernel is a TPU backend's
    choice (ops/attention.paged_kernel_applies) and this process's backend
    is the CPU, so the test says "tpu" in its place."""
    from distributed_lion_tpu.models.gpt2 import (
        GPT2Config, gpt2_decode_paged, gpt2_init,
    )
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.ops.attention import paged_copy_pages
    from distributed_lion_tpu.serve.kv_cache import init_pages

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPT2Config.gpt2_124m()
    block, per_seq = 16, 64
    b, s_len = (32, 1) if kind == "decode_tick" else (1, 512)

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    pages = place(jax.eval_shape(lambda: init_pages(
        cfg.n_layer, 32 * per_seq, block, cfg.n_head, cfg.head_dim,
        cfg.compute_dtype)))
    leaf = pages[0]["k"]

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if kind == "cow":
        compiled = jax.jit(paged_copy_pages, donate_argnums=(0,)).lower(
            pages, i32(32), i32(32)).compile()
    else:
        params = place(jax.eval_shape(
            lambda: gpt2_init(jax.random.key(0), cfg)))

        def fn(params, pages, toks, tables, pos):
            valid = jnp.arange(s_len)[None, :] < jnp.maximum(pos[:, None], 1)
            logits, pages = gpt2_decode_paged(params, toks, cfg, pages,
                                              tables, pos, valid)
            return jnp.argmax(logits[:, -1], -1), pages

        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, pages, i32(b, s_len), i32(b, per_seq), i32(b)).compile()
    text = compiled.as_text()
    assert "input_output_alias" in text
    assert not pool_leaf_copies(text, leaf)
    assert bool(_named_custom_call(text, "paged_attn")) == (
        kind == "decode_tick")
    if kind == "prefill_bucket":
        assert re.search(r'op_name="[^"]*/paged_gather/', text)


@pytest.mark.parametrize("bucket", [1024, 512])
def test_gpt2_xl_prefill_from_0_attends_over_fresh_keys(one_chip, bucket,
                                                         monkeypatch):
    """Cell 2's prefill at both of its buckets, GPT-2 XL's 48 layers of 25
    heads of 64 over the pool as the engine lays it out, donated, told that
    it starts at position 0: every layer attends through ``flash_gqa_fwd``
    (lowered ONCE: the blocks are a Python loop and the wrapper is jitted),
    no float32 scores of the table's width exist, and the pool is written
    (a page at a time: ``paged_scatter_fresh``) and never gathered or
    copied."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.models.gpt2 import (
        GPT2Config, gpt2_decode_paged, gpt2_init,
    )
    from distributed_lion_tpu.serve.kv_cache import init_pages

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPT2Config(vocab_size=50257, n_layer=48, n_head=25, d_model=1600,
                     n_ctx=1024)
    block, per_seq = 16, 64

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = place(jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),       # the cell's weights: 3.1 GB
        gpt2_init(jax.random.key(0), cfg))))
    pages = place(jax.eval_shape(lambda: init_pages(
        cfg.n_layer, 32 * per_seq, block, cfg.n_head, cfg.head_dim,
        cfg.compute_dtype)))
    assert pages[0]["k"].shape == (2048, 16, 1, 1664)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(params, pages, toks, tables, pos, length):
        valid = jnp.arange(bucket)[None, :] < length
        logits, pages = gpt2_decode_paged(params, toks, cfg, pages, tables,
                                          pos, valid, fresh=True)
        return jnp.argmax(logits[0, length - 1], -1), pages

    t0 = time.monotonic()
    lowered = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pages, i32(1, bucket), i32(1, per_seq), i32(1), i32())
    assert lowered.as_text().count("tpu_custom_call") == 1
    text = lowered.compile().as_text()
    assert time.monotonic() - t0 < 240
    calls = re.findall(r"%flash_gqa_fwd(?:\.\d+)? = [^\n]*custom-call", text)
    assert len(calls) == cfg.n_layer
    assert "input_output_alias" in text
    assert not pool_leaf_copies(text, pages[0]["k"])
    assert f"f32[1,25,{bucket},1024]" not in text
    assert not re.search(r'op_name="[^"]*/paged_(gather|attn)/', text)
    assert re.search(r'op_name="[^"]*/paged_scatter/', text)
    # k and v of every layer are written as whole [16, 1664] pages
    assert len(re.findall(r" scatter\([^\n]*update_window_dims=\{1,2\}",
                          text)) == 2 * cfg.n_layer
    # what the pool meets is the scatter alone: no gather reads 2,048 pages
    assert not re.search(r"gather\([^\n]*bf16\[2048,16,1,1664\]", text)


def test_mla_kernel_compiles_at_published_widths(one_chip):
    """``mla_paged_attn`` at JoyAI-LLM-Flash's widths and the cell's pool:
    32 absorbed queries of 640 lanes (576 of them the latent row) a
    sequence, 128 sequences, pages of 16 rows read where they lie."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.ops.pallas_mla_attn import mla_paged_attn
    from distributed_lion_tpu.serve.kv_cache import pool_row_width

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    width = pool_row_width(1, 512 + 64)
    assert width == 640
    leaf = on_chip((128 * 192, 16, 1, width))
    text, secs = _compile(
        lambda q, kv, t, n: mla_paged_attn(q, kv, t, n, scale=192 ** -0.5),
        on_chip((128, 32, width)), leaf, on_chip((128, 192), jnp.int32),
        on_chip((128,), jnp.int32))
    assert secs < 60
    assert _named_custom_call(text, "mla_paged_attn")
    assert not pool_leaf_copies(text, leaf)


@pytest.mark.parametrize("m,k,n", [(1024, 2048, 768), (1024, 768, 2048),
                                   (16384, 2048, 768), (16384, 768, 2048)],
                         ids=["decode_up", "decode_down", "prefill_up",
                              "prefill_down"])
def test_moe_gmm_kernel_compiles_at_published_widths(one_chip, m, k, n):
    """The grouped matmul over 256 experts' banks at a decode tick's 1,024
    assignments and a 2,048-token prefill's 16,384: Mosaic takes a whole
    ``[2048, 768]`` bank as one block, and no bank is copied."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.ops.pallas_moe_gmm import moe_gmm

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bank = on_chip((256, k, n))
    text, secs = _compile(moe_gmm, on_chip((m, k)), bank,
                          on_chip((256,), jnp.int32))
    assert secs < 60
    assert _named_custom_call(text, "moe_gmm")
    assert not pool_leaf_copies(text, bank)


@pytest.mark.parametrize("m,k,n", [(640, 3072, 1024), (40960, 3072, 1024),
                                   (40960, 1024, 3072)],
                         ids=["decode_up", "prefill_up", "prefill_down"])
def test_moe_gmm_kernel_told_of_a_tail_compiles(one_chip, m, k, n):
    """The grouped matmul as the layer that holds 128 of 256 experts calls
    it (``tail``: the picks held elsewhere lie past the last group), at a
    decode tick's 640 assignments and a 4,096-token slice's 40,960 over
    ``[3072, 1024]`` banks: one scalar operand more, no bank copied."""
    import functools

    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.ops.pallas_moe_gmm import moe_gmm

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bank = on_chip((128, k, n))
    text, secs = _compile(functools.partial(moe_gmm, tail=True),
                          on_chip((m, k)), bank, on_chip((128,), jnp.int32))
    assert secs < 60
    assert _named_custom_call(text, "moe_gmm")
    assert not pool_leaf_copies(text, bank)


def test_trained_expert_layer_bounds_the_combines_transpose_at_cell_10_widths(
        one_chip, monkeypatch):
    """``moe_dropless_ffn`` and its gradient as cell 10 runs a microbatch
    (16,384 tokens, top 8 of 64, 16 held, 2,304 wide, experts of 896,
    bfloat16): the combine's transpose is a loop over chunks of sorted rows
    (its trip count is read on the device) that writes ``dy`` over ``y``
    where it lies, the grouped products stay the kernels, and the program
    holds no more under the `full` rung than the plain one (no cotangent in
    pick order, no second gather of the rows back)."""
    from distributed_lion_tpu.parallel import expert

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n, k, d, f = 16384, 8, 2304, 896

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"router": on_chip((64, d), jnp.float32),
              "w_gate": on_chip((16, d, f)), "w_up": on_chip((16, d, f)),
              "w_down": on_chip((16, f, d))}

    def loss(params, x):     # under the `full` rung, as the cell runs it
        y, counters = jax.checkpoint(lambda params, x: expert.moe_dropless_ffn(
            params, x, top_k=k, scale=1.0, held=(0, 16),
            return_counters=True))(params, x)
        return y.astype(jnp.float32).sum(), counters

    def compiled():
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True)).lower(
            params, on_chip((n, d))).compile()

    mine = compiled()
    text = mine.as_text()
    for kernel in ("moe_gmm", "moe_gmm_drhs"):
        assert _named_custom_call(text, kernel), kernel
    assert re.search(r"moe/combine\)*/while", text)
    assert re.search(r"bf16\[%d,%d\]\S* dynamic-update-slice\(" % (n * k, d),
                     text)
    monkeypatch.setattr(expert, "ROW_CHUNK", n * k + 1)   # the plain program
    plain = compiled()
    assert not re.search(r"moe/combine\)*/while", plain.as_text())
    assert mine.memory_analysis().temp_size_in_bytes \
        <= plain.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("width", [4096, 512], ids=["q", "k"])
def test_qk_rope_kernels_compile_at_cell_10_widths(one_chip, width):
    """``qk_rope_fwd`` / ``qk_rope_bwd`` (ops/pallas_qk_rope) over a
    microbatch of 2 x 8,192 rows of 32 and of 4 heads, bfloat16."""
    from distributed_lion_tpu.ops.pallas_qk_rope import qk_norm_rope

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(y, scale, cos, sin, w):
        out, back = jax.vjp(
            lambda y, scale: qk_norm_rope(y, scale, cos, sin, 1e-6), y, scale)
        return (out,) + back(w)

    y = on_chip((2, 8192, width), jnp.bfloat16)
    text, secs = _compile(both, y, on_chip((128,)), on_chip((8192, 64)),
                          on_chip((8192, 64)), y)
    assert secs < 30
    for kernel in ("qk_rope_fwd", "qk_rope_bwd"):
        assert _named_custom_call(text, kernel), kernel


def test_mellum_window_layer_norms_and_rotates_where_the_projection_wrote(
        one_chip, monkeypatch):
    """The gradient of one window layer at cell 10's size (``x +
    _attention(_rms_norm(x))`` under ``jax.checkpoint``, 2 x 8,192 rows of
    2,304, bfloat16): q and k go from their projections to ``flash_gqa``
    through the one kernel a direction, so nothing takes the 4-D head shape
    whose tiling the compiler lays over (head, lane) (``[16384,32,1,128]``,
    ``[16384,4,1,128]``: every crossing was a relayout of 134 MB) and no
    ``reshape`` of q's size is a real copy."""
    from distributed_lion_tpu.models import mellum
    from distributed_lion_tpu.models.llama import _rms_norm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = mellum.MellumConfig()

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    p = jax.tree.map(on_chip, jax.eval_shape(
        lambda: mellum.mellum_init(jax.random.key(0), dataclasses.replace(
            cfg, n_layer=1, vocab_size=128))["blocks"][0]))

    @jax.checkpoint
    def layer(x, p):
        return x + mellum._attention(
            _rms_norm(x, p["ln_attn"], cfg.rms_eps), p["attn"], cfg, True)

    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    text, secs = _compile(jax.grad(
        lambda x, p: layer(x, p).astype(jnp.float32).sum(), (0, 1)), x, p)
    assert secs < 40
    for kernel in ("qk_rope_fwd", "qk_rope_bwd", "flash_gqa_lse",
                   "flash_gqa_dq", "flash_gqa_dkv"):
        assert _named_custom_call(text, kernel), kernel
    assert not re.search(r"= \w+\[16384,(32|4),1,", text)
    real_reshapes = [
        m.group(0)[:160] for m in re.finditer(
            r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* reshape\(.*$",
            text, re.M)
        if np.prod([int(d) for d in m.group(1).split(",")]) >= 2 ** 25]
    assert not real_reshapes, real_reshapes      # a bitcast is another opcode


def test_train_blocks_compile_under_the_workers_shard_map(topo, monkeypatch):
    """Cell 4's path: the same two blocks, plain as its ``auto`` leaves
    them, inside a ``shard_map`` over the four chips' ``data`` axis, 4
    sequences a worker: each worker runs the repo's flash kernels on its
    own shard, no collective among them."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    loss, blocks = _two_blocks(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=repl), "none")
    x = jax.ShapeDtypeStruct((16, 1024, 768), jnp.bfloat16, sharding=split)

    def worker(blocks, x):
        g = jax.grad(loss)(blocks, x)
        return jax.tree.map(lambda a: a[None], g)   # a worker's own gradient

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = jax.shard_map(worker, mesh=mesh, in_specs=(P(), P("data")),
                         out_specs=P("data"), check_vma=False)
    text, _ = _compile(step, blocks, x)
    assert _named_custom_call(text, "flash_attention_fwd")
    assert _named_custom_call(text, "flash_mha_bwd")
    assert not re.search(r"all-(reduce|gather|to-all)", text)


@pytest.mark.parametrize("kind", ["decode_tick", "prefill_bucket", "cow"])
def test_latent_serving_programs_read_pool_and_banks_in_place(
        one_chip, kind, monkeypatch):
    """JoyAI-LLM-Flash's three serving programs at the published widths
    (one dense and one expert layer, the whole vocabulary) over the latent
    pool as the engine lays it out, donated: none copies the pool's one
    leaf or an expert bank or builds an ``[E, tokens, D]`` buffer; the
    decode tick holds ``mla_paged_attn`` and ``moe_gmm``, the prefill keeps
    the gather for attention and takes ``moe_gmm`` for its experts."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.models.joyai import (
        JoyAIConfig, joyai_decode_paged, joyai_init,
    )
    from distributed_lion_tpu.ops.attention import paged_copy_pages
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = JoyAIConfig(n_layer=2)
    block, per_seq, slots = 16, 192, 128
    # bucket 1,024 (2,048 tokens would read as an expert bank's d_model)
    b, s_len = (slots, 1) if kind == "decode_tick" else (1, 1024)

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    pages = place(jax.eval_shape(lambda: init_page_leaves(
        cfg.n_layer, slots * per_seq, block, {"kv": (1, cfg.latent_dim)},
        cfg.compute_dtype)))
    leaf = pages[0]["kv"]
    assert leaf.shape == (slots * per_seq, block, 1, 640)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    if kind == "cow":
        compiled = jax.jit(paged_copy_pages, donate_argnums=(0,)).lower(
            pages, i32(slots), i32(slots)).compile()
    else:
        params = place(jax.eval_shape(
            lambda: joyai_init(jax.random.key(0), cfg)))

        def fn(params, pages, toks, tables, pos):
            valid = jnp.arange(s_len)[None, :] < jnp.maximum(pos[:, None], 1)
            logits, pages, st = joyai_decode_paged(
                params, toks, cfg, pages, tables, pos, valid, True,
                None if kind == "decode_tick" else pos[0])
            return (jnp.argmax(logits[:, -1], -1), st), pages

        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            params, pages, i32(b, s_len), i32(b, per_seq), i32(b)).compile()
    text = compiled.as_text()
    assert "input_output_alias" in text
    assert not pool_leaf_copies(text, leaf)
    if kind == "cow":
        return
    bank = jax.ShapeDtypeStruct((cfg.n_experts, cfg.d_model, cfg.moe_d_ff),
                                jnp.bfloat16)
    assert not pool_leaf_copies(text, bank)
    for m in re.finditer(r"= \w+\[([\d,]+)\]", text):   # [E, tokens, .]
        dims = [int(d) for d in m[1].split(",")]
        assert len(dims) < 3 or dims[:2] != [cfg.n_experts, b * s_len], m[0]
    assert _named_custom_call(text, "moe_gmm")
    assert bool(_named_custom_call(text, "mla_paged_attn")) == (
        kind == "decode_tick")
    for scope in ("mla/q", "mla/kv_latent", "mla_attn", "moe/route",
                  "moe/sort", "moe/experts", "moe/shared", "moe/combine"):
        assert re.search(r'op_name="[^"]*/%s/' % scope, text), scope
    if kind == "prefill_bucket":
        assert re.search(r'op_name="[^"]*/paged_gather/', text)
        # one position's logits, not 1,024 x 129,280 of them
        assert not re.search(r"f32\[1,1024,129280\]", text)


@pytest.mark.parametrize("kind", ["decode_tick", "prefill_8192",
                                  "prefill_4096"])
def test_window_and_full_serving_programs_at_the_published_shapes(
        one_chip, kind, monkeypatch):
    """Laguna-S-2.1 as ``serve.laguna-s-2.1.backlog-8k`` runs it (the cut
    configuration file: 5 layers, 128 of 256 experts held, half the
    vocabulary; 64 slots, 20,480 pages, rings of 33), donated. The decode
    tick holds ``paged_attn`` once a layer, both kinds (48 and 72 queries
    over the same 1,024 lanes), and ``moe_gmm`` over the 128 banks held; no
    program copies a pool leaf, a ring or a bank. A prefill (the longest
    bucket, and the 4,096 bucket of the median prompt) holds no float32
    ``[heads, S, S]`` scores, no ``[tokens, 10, 3072]`` float32 buffer, no
    ``[E, tokens, D]`` buffer and one position's logits, and fits the chip
    beside 14.24 GB of weights and cache."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.models.laguna import (
        LAGUNA_COUNTERS, LagunaConfig, laguna_decode_paged, laguna_init,
    )
    from distributed_lion_tpu.ops.attention import ring_pages
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = LagunaConfig.named(os.path.join(
        root, "benchmark", "configs", "laguna-s-2.1.json"))
    block, per_seq, slots, pool = 16, 560, 64, 20480
    ring = ring_pages(cfg.window, block)
    b, s_len = (slots, 1) if kind == "decode_tick" \
        else (1, int(kind.split("_")[1]))

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    kv = (cfg.n_kv_head, cfg.head_dim)
    pages = place(jax.eval_shape(lambda: init_page_leaves(
        cfg.n_layer, pool, block, {"k": kv, "v": kv}, cfg.compute_dtype,
        ring=(cfg.window_layers, slots * ring))))
    assert [p["k"].shape for p in pages] == [
        (n, block, 1, 1024) for n in (pool, 2112, 2112, 2112, pool)]
    params = place(jax.eval_shape(
        lambda: laguna_init(jax.random.key(0), cfg)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert round(n_params / 1e6) == 5572                      # 11.14 GB

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(params, pages, toks, tables, owned, pos):
        valid = jnp.arange(s_len)[None, :] < jnp.maximum(pos[:, None], 1)
        logits, pages, st = laguna_decode_paged(
            params, toks, cfg, pages, tables, owned,
            pos if kind == "decode_tick" else jnp.zeros_like(pos), valid,
            True, None if kind == "decode_tick" else pos[0])
        tail = jnp.stack([st[k] for k in LAGUNA_COUNTERS])
        return (jnp.argmax(logits[:, -1], -1), tail), pages

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pages, i32(b, s_len), i32(b, per_seq), i32(b),
        i32(b)).compile()
    text = compiled.as_text()
    assert "input_output_alias" in text
    for leaf in (pages[0]["k"], pages[1]["k"]):       # pages, then a ring
        assert not pool_leaf_copies(text, leaf)
    bank = jax.ShapeDtypeStruct((cfg.banks, cfg.d_model, cfg.moe_d_ff),
                                jnp.bfloat16)
    assert cfg.banks == 128 and not pool_leaf_copies(text, bank)
    assert not re.search(r"bf16\[256,(3072,1024|1024,3072)\]", text)
    tokens = b * s_len
    # buffers: what an instruction of a fused computation yields lives in
    # registers and VMEM, so those bodies are set aside
    held = re.sub(r"(?ms)^%?fused_computation[^\n]*\{\n.*?^\}\n", "", text)
    assert "fusion(" in held and len(held) < len(text)
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]", held):
        dims = [int(d) for d in m[2].split(",")]
        assert len(dims) < 3 or dims[:2] != [cfg.banks, tokens], m[0]
        if m[1] == "f32" and kind != "decode_tick":
            size = 1
            for d in dims:
                size *= d
            # scores of a whole row would be 48 x 8,192 x 8,192; the
            # combine of a whole prefill 8,192 x 10 x 3,072
            assert size < 8192 * 10 * 3072, m[0]
    assert _named_custom_call(text, "moe_gmm")
    calls = re.findall(r"%paged_attn(?:\.\d+)? = [^\n]*custom-call", text)
    assert len(calls) == (cfg.n_layer if kind == "decode_tick" else 0)
    # a prefill's two full layers go through the tiled kernel
    calls = re.findall(r"%flash_gqa_fwd(?:\.\d+)? = [^\n]*custom-call", text)
    assert len(calls) == (0 if kind == "decode_tick" else 2)
    for scope in ("attn/qkv", "attn/rope", "attn/gate", "window_attn",
                  "full_attn", "moe/route", "moe/sort", "moe/experts",
                  "moe/shared", "moe/combine"):
        assert re.search(r'op_name="[^"]*/%s/' % scope, text), scope
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 14.2e9 < mem.argument_size_in_bytes < 14.3e9
    assert live < 15.75 * 2 ** 30 - 0.5e9, live               # the chip's HBM
    if kind != "decode_tick":
        assert not re.search(r"f32\[1,%d,50176\]" % s_len, text)
        assert mem.temp_size_in_bytes < 2.0e9
        # the compiler's cost estimate of a fusion overflows where it tiles
        # it badly: a full layer's softmax over 8,192 keys at 128 queries a
        # chunk did, and ran 64 times slower on the chip than at 32 (PR 30)
        assert '"estimated_cycles":"9223372036854775807"' not in text


@pytest.mark.parametrize("kind", ["decode_tick", "prefill_4096"])
def test_state_and_latent_serving_programs_at_the_published_shapes(
        one_chip, kind, monkeypatch):
    """Ling-3.0-flash-VL as ``serve.ling-3.0-flash-vl.backlog-1k-long`` runs
    it (the cut configuration file: 7 layers, 128 of 512 experts held, a
    quarter of the vocabulary; 128 slots, 40,960 latent pages, 1.61 GB of
    float32 state in six KDA layers), donated. The decode tick holds
    ``kda_step`` once a KDA layer, ``mla_paged_attn`` once and ``moe_gmm``
    over the 128 banks held, and steps the state IN PLACE: no program copies
    a state leaf, a convolution tail, the latent pool or a bank, and the
    tick's temporaries are a few tens of MB beside 2.5 GB of cache. The
    4,096-token prefill (the longest bucket) runs the chunked rule as
    ``kda_chunk`` once a KDA layer (no ``[chunk, chunk]`` product of it in
    memory), keeps one position's logits, and fits the chip beside 12.84 GB
    of weights and cache."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.models.ling import (
        LING_COUNTERS, LingConfig, ling_decode_paged, ling_init,
    )
    from distributed_lion_tpu.serve.engine import ServeModel
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = LingConfig.named(os.path.join(
        root, "benchmark", "configs", "ling-3.0-flash-vl.json"))
    block, per_seq, slots, pool = 16, 512, 128, 40960
    b, s_len = (slots, 1) if kind == "decode_tick" \
        else (1, int(kind.split("_")[1]))

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    model = ServeModel.for_ling(None, cfg)
    pages = place(jax.eval_shape(lambda: init_page_leaves(
        cfg.n_layer, pool, block, model.page_leaves, cfg.compute_dtype,
        state=(cfg.kda_layers, slots, model.state_leaves))))
    assert [sorted(p) for p in pages] == [["conv", "state"]] * 5 \
        + [["kv"], ["conv", "state"]]
    assert pages[0]["state"].shape == (slots, 32, 128, 128)
    assert pages[0]["conv"].shape == (slots, 3, 12288)
    assert pages[5]["kv"].shape == (pool, block, 1, 640)
    params = place(jax.eval_shape(lambda: ling_init(jax.random.key(0), cfg)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert round(n_params / 1e6) == 5169                      # 10.34 GB

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(params, pages, toks, tables, owned, pos):
        valid = jnp.arange(s_len)[None, :] < jnp.maximum(pos[:, None], 1)
        logits, pages, st = ling_decode_paged(
            params, toks, cfg, pages, tables, owned,
            pos if kind == "decode_tick" else jnp.zeros_like(pos), valid,
            True, None if kind == "decode_tick" else pos[0])
        tail = jnp.stack([st[k] for k in LING_COUNTERS])
        return (jnp.argmax(logits[:, -1], -1), tail), pages

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pages, i32(b, s_len), i32(b, per_seq), i32(b),
        i32(b)).compile()
    text = compiled.as_text()
    assert "input_output_alias" in text
    for leaf in (pages[0]["state"], pages[0]["conv"], pages[5]["kv"]):
        assert not pool_leaf_copies(text, leaf)
    bank = jax.ShapeDtypeStruct((cfg.banks, cfg.d_model, cfg.moe_d_ff),
                                jnp.bfloat16)
    assert cfg.banks == 128 and not pool_leaf_copies(text, bank)
    assert not re.search(r"bf16\[512,(2560,768|768,2560)\]", text)
    held = re.sub(r"(?ms)^%?fused_computation[^\n]*\{\n.*?^\}\n", "", text)
    assert "fusion(" in held and len(held) < len(text)
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]", held):
        dims = [int(d) for d in m[2].split(",")]
        # an [E, tokens, D] buffer (128 slots beside 128 banks: all three)
        assert dims[:3] != [cfg.banks, b * s_len, cfg.d_model], m[0]
        # the chunked rule's products a chunk (the XLA form's, ops/kda)
        assert dims[-3:] != [16, 16, 128] and dims[-2:] != [64, 64], m[0]
    assert _named_custom_call(text, "moe_gmm")
    decode = kind == "decode_tick"
    calls = re.findall(r"%kda_step(?:\.\d+)? = [^\n]*custom-call", text)
    assert len(calls) == (len(cfg.kda_layers) if decode else 0) == 6 * decode
    calls = re.findall(r"%kda_chunk(?:\.\d+)? = [^\n]*custom-call", text)
    assert len(calls) == 6 * (not decode)
    calls = re.findall(r"%mla_paged_attn(?:\.\d+)? = [^\n]*custom-call",
                       text)
    assert len(calls) == decode
    for scope in ("kda/conv", "kda/gate", "kda/step" if decode
                  else "kda/chunk", "attn/gate", "mla/q", "mla/kv_latent",
                  "moe/route", "moe/groups", "moe/sort", "moe/experts",
                  "moe/shared", "moe/combine"):
        assert re.search(r'op_name="[^"]*/%s/' % scope, text), scope
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 12.8e9 < mem.argument_size_in_bytes < 12.9e9
    assert mem.alias_size_in_bytes > 2.5e9          # state, tails and pool
    assert live < 15.75 * 2 ** 30 - 0.5e9, live               # the chip's HBM
    if decode:
        # a copy of the state leaves would be 1.61 GB of temporaries
        assert mem.temp_size_in_bytes < 0.2e9, mem.temp_size_in_bytes
    else:
        assert not re.search(r"f32\[1,%d,39296\]" % s_len, text)
        assert mem.temp_size_in_bytes < 2.2e9
        assert '"estimated_cycles":"9223372036854775807"' not in text


def test_kda_step_kernel_compiles_at_the_published_shape(one_chip):
    """``kda_step`` over 128 slots of 32 heads of 128 x 128 float32, the
    state aliased in and out."""
    from distributed_lion_tpu.ops.pallas_kda import kda_step

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    vec = on_chip((128, 32, 128))
    t0 = time.monotonic()
    compiled = jax.jit(kda_step, donate_argnums=(0,)).lower(
        on_chip((128, 32, 128, 128)), vec, vec, vec, vec, on_chip((128, 32)),
        on_chip((128,), jnp.bool_)).compile()
    assert time.monotonic() - t0 < 60
    text = compiled.as_text()
    assert _named_custom_call(text, "kda_step")
    assert "input_output_alias" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.1e9, mem.temp_size_in_bytes


def test_kda_chunk_kernel_compiles_at_the_published_shape(one_chip):
    """``kda_chunk`` over a 4,096-token prompt's 32 heads of 128 x 128: a
    head's lanes are read where the projections left them (no head-major
    copy of q, k, v or g around the kernel)."""
    from distributed_lion_tpu.ops.pallas_kda import chunk_kernel_takes, kda_chunk

    def on_chip(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    rows = on_chip(1, 4096, 32, 128)
    assert chunk_kernel_takes((1, 32, 128, 128), jnp.float32)
    t0 = time.monotonic()
    compiled = jax.jit(kda_chunk).lower(
        rows, rows, rows, rows, on_chip(1, 4096, 32),
        on_chip(1, 32, 128, 128)).compile()
    assert time.monotonic() - t0 < 60
    text = compiled.as_text()
    assert _named_custom_call(text, "kda_chunk")
    assert not re.search(r"f32\[1,32,4096,128\]", text)      # head-major
    mem = compiled.memory_analysis()
    # beta k and the padded operands: nothing a chunk squared
    assert mem.temp_size_in_bytes < 0.3e9, mem.temp_size_in_bytes
    # a caller's float32 context (chip_smoke's comparison in float32) must
    # not reach the kernel's bfloat16 passes: Mosaic refuses that product
    rows = on_chip(1, 256, 32, 128)
    with jax.default_matmul_precision("highest"):
        jax.jit(lambda *a: kda_chunk.__wrapped__(*a)).lower(
            rows, rows, rows, rows, on_chip(1, 256, 32),
            on_chip(1, 32, 128, 128)).compile()


def test_tp_decode_tick_runs_the_kernel_shard_local(topo, monkeypatch):
    """The TP engine's decode tick on two chips of the described mesh:
    inside ``shard_map`` every rank holds its own kv-head group of the pool
    (``[num_blocks, block_size, 1, W/tp]``) and runs ``paged_attn`` on it;
    the only collectives stay the two row-parallel psums a layer."""
    from distributed_lion_tpu.models.gpt2 import (
        GPT2Config, gpt2_decode_paged, gpt2_init,
    )
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.parallel.mesh import TENSOR_AXIS
    from distributed_lion_tpu.parallel.tensor_parallel import gpt2_param_specs
    from distributed_lion_tpu.serve.kv_cache import init_pages

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = GPT2Config.gpt2_124m()
    tp, block, per_seq, b = 2, 16, 64, 32
    mesh = Mesh(np.asarray(topo.devices[:tp]), (TENSOR_AXIS,))
    specs = gpt2_param_specs(cfg)
    pool_spec = P(None, None, TENSOR_AXIS, None)

    def place(tree, spec_tree):
        return jax.tree.map(
            lambda x, sp: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, sp)),
            tree, spec_tree)

    params = place(jax.eval_shape(lambda: gpt2_init(jax.random.key(0), cfg)),
                   specs)
    pages = jax.eval_shape(lambda: init_pages(
        cfg.n_layer, b * per_seq, block, cfg.n_head, cfg.head_dim,
        cfg.compute_dtype, groups=tp))
    pages_spec = jax.tree.map(lambda _: pool_spec, pages)
    pages = place(pages, pages_spec)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(mesh, P()))

    def tick(params, pages, toks, tables, pos):
        logits, pages = gpt2_decode_paged(params, toks, cfg, pages, tables,
                                          pos, tp_axis=TENSOR_AXIS)
        return jnp.argmax(logits[:, -1], -1), pages

    body = jax.shard_map(tick, mesh=mesh,
                         in_specs=(specs, pages_spec, P(), P(), P()),
                         out_specs=(P(), pages_spec), check_vma=False)
    text = jax.jit(body, donate_argnums=(1,)).lower(
        params, pages, i32(b, 1), i32(b, per_seq), i32(b)).compile().as_text()
    local = jax.ShapeDtypeStruct((b * per_seq, block, 1, 384),
                                 cfg.compute_dtype)
    assert "input_output_alias" in text
    assert _named_custom_call(text, "paged_attn")
    assert not pool_leaf_copies(text, local)
    assert len(re.findall(r" all-reduce(-start)?\(", text)) <= 2 * cfg.n_layer


# --------------------------------------------------------- 4-device vote step
def test_vote_step_compiles_on_2x2_mesh_with_auto_wire(topo):
    """The optimizer step on a Mesh of the described 2x2: the wire, bucket
    count and kernel mode the trainer's auto rule resolves to on one host of
    four chips (packed_a2a, 4 buckets past 16M coordinates, Pallas)."""
    from distributed_lion_tpu.ops import pallas_lion
    from distributed_lion_tpu.optim import distributed_lion, init_global_state
    from distributed_lion_tpu.optim.sharded import make_sharded_step
    from distributed_lion_tpu.train.loop import TrainConfig, resolve_auto_comm

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    shapes = {"wte": (25_000, 768), "fc": (768, 3072), "b": (1001,)}
    n = sum(int(np.prod(s)) for s in shapes.values())
    cfg = resolve_auto_comm(TrainConfig(), mesh, n, params_replicated=True)
    assert (cfg.wire, cfg.vote_buckets) == ("packed_a2a", 4)

    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=repl)
              for k, s in shapes.items()}
    grads = {k: jax.ShapeDtypeStruct((4,) + s, jnp.float32, sharding=split)
             for k, s in shapes.items()}
    # the test steers what the code would ask the (absent) chip: on a TPU
    # backend kernel='auto' is the compiled Pallas path
    real = pallas_lion.pallas_available
    pallas_lion.pallas_available = lambda: True
    try:
        opt = distributed_lion(learning_rate=1e-4, weight_decay=0.1,
                               wire=cfg.wire, vote_buckets=cfg.vote_buckets,
                               kernel="auto")
    finally:
        pallas_lion.pallas_available = real
    state = jax.eval_shape(lambda: init_global_state(
        opt, {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()},
        world=4))
    state = state._replace(
        count=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        exp_avg={k: jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=split)
                 for k, m in state.exp_avg.items()})
    t0 = time.monotonic()
    text = make_sharded_step(opt, mesh).lower(
        params, grads, state).compile().as_text()
    assert time.monotonic() - t0 < 120
    assert "tpu_custom_call" in text
    assert _named_custom_call(text, "lion_ballot")
    assert _named_custom_call(text, "lion_apply")
    for scope in ("vote/pack", "vote/unpack", "vote/tally", "vote/wire"):
        assert re.search(r'op_name="[^"]*/%s/' % scope, text), scope
    # phase 1 of the packed wire; the compiler is free to rewrite phase 2's
    # all_gather (it becomes dynamic-update-slice + all-reduce on v5e)
    assert "all-to-all" in text


def test_vote_step_takes_gpt2_leaves_where_they_lie(topo):
    """The optimizer's step for the described 2x2 at GPT-2's leaf shapes
    (``wte`` split by bucket boundaries, the fused qkv's 3-D form, a bias):
    between the gradient and the new parameters XLA relays, slices, pads,
    joins or reshapes no float32 array of 2^20 elements, the kernels keep
    the names ``lion_ms.train`` reads them by, and there are as many Mosaic
    calls as the trainer's ``[setup] lion:`` line says.

    One relayout is not the step's to remove and is allowed by name: a
    worker's momentum is stored stacked, ``[W, 50257, 768]``, and the chip
    keeps a ``[1, R, C]`` shard whose ``R`` is off the sublane tile in a
    row-linear layout (``{2,0,1:T(1,128)}``), so ``exp_avg['wte']`` (and
    here the stacked gradient, which a trainer computes in place) is
    copied into row tiles on the way in and back on the way out."""
    from distributed_lion_tpu.ops import pallas_lion
    from distributed_lion_tpu.ops.codec import bucket_bounds
    from distributed_lion_tpu.optim import distributed_lion, init_global_state
    from distributed_lion_tpu.optim.sharded import make_sharded_step

    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    shapes = {"fc": (768, 3072), "fc_b": (3072,), "qkv": (768, 3, 768),
              "qkv_b": (3, 768), "wte": (50257, 768)}
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=repl)
              for k, s in shapes.items()}
    grads = {k: jax.ShapeDtypeStruct((4,) + s, jnp.float32, sharding=split)
             for k, s in shapes.items()}
    real = pallas_lion.pallas_available
    pallas_lion.pallas_available = lambda: True
    try:
        opt = distributed_lion(learning_rate=1e-4, weight_decay=0.1,
                               wire="packed_a2a", vote_buckets=4,
                               kernel="auto")
    finally:
        pallas_lion.pallas_available = real
    state = jax.eval_shape(lambda: init_global_state(
        opt, {k: jnp.zeros(s, jnp.float32) for k, s in shapes.items()},
        world=4))
    state = state._replace(
        count=jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        exp_avg={k: jax.ShapeDtypeStruct(m.shape, m.dtype, sharding=split)
                 for k, m in state.exp_avg.items()})
    # parameters and state donated, as the Trainer's train step donates them
    text = jax.jit(make_sharded_step(opt, mesh), donate_argnums=(0, 2)).lower(
        params, grads, state).compile().as_text()

    leaves = [shapes[k] for k in sorted(shapes)]
    n = sum(int(np.prod(s)) for s in leaves)
    layout = pallas_lion.leaf_layout(leaves,
                                     bucket_bounds(n, 4, 4, "packed_a2a"))
    assert layout.line() == (
        "[setup] lion: 3 leaves in place (100.0% of coordinates), 2 through "
        "the flat path, 22 kernel calls a step")
    # bucket boundaries fall inside wte: a block of it waits for two buckets
    assert any(len({b for b, _, _ in v}) > 1 for v in layout.verdicts)
    assert text.count('custom_call_target="tpu_custom_call"') == layout.calls
    assert _named_custom_call(text, "lion_ballot")
    assert _named_custom_call(text, "lion_apply")
    ops = _big_f32_layout_ops(text)
    stacked = [op for op in ops if " = f32[1,50257,768]{" in op]
    assert [op for op in ops if op not in stacked] == []
    # momentum in and out, and this harness's stacked gradient in
    assert len(stacked) <= 3 and all(" copy(" in op for op in stacked)


@pytest.mark.parametrize("kind", ["decode_tick", "prefill_16384"])
def test_minicpm_sala_serving_programs_at_the_published_shapes(
        one_chip, kind, monkeypatch):
    """MiniCPM-SALA as ``serve.minicpm-sala.backlog-16k`` runs it (the cut
    configuration file: layers 9-16, the whole vocabulary; 64 slots, 81,920
    pages of keys, values and one compressed key each in two ``minicpm4``
    layers, 0.81 GB of float32 state in six Lightning layers), donated. The
    decode tick holds ``lightning_step`` once a Lightning layer and
    ``paged_attn`` once a ``minicpm4`` layer (over the compacted lists of
    128 (row, kv head) pairs, a selected block of 64 positions one entry:
    the kernel's k and v operands are the pool leaves seen as 20,480 runs of
    64 rows, a bitcast), steps the state and writes the pages IN PLACE
    (no copy of 0.81 or 2.77 GB, nor of a leaf under either view: a copy is
    matched by its size), and selects in XLA. The 16,384-token
    prefill (the one bucket) runs ``lightning_chunk`` once a Lightning layer,
    the dense half of a ``minicpm4`` layer through ``flash_gqa_fwd`` at
    8,192, the selected half a tile of queries at a time: no ``[32, 16384,
    16384]`` buffer outside a fused body, one position's logits, and it fits
    the chip beside 9.2 GB of weights and cache."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.models.minicpm_sala import (
        SALA_COUNTERS, MiniCPMSalaConfig, minicpm_sala_decode_paged,
        minicpm_sala_init,
    )
    from distributed_lion_tpu.serve.engine import ServeModel
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = MiniCPMSalaConfig.named(os.path.join(
        root, "benchmark", "configs", "minicpm-sala.json"))
    block, per_seq, slots, pool = 16, 1280, 64, 81920
    b, s_len = (slots, 1) if kind == "decode_tick" \
        else (1, int(kind.split("_")[1]))

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    model = ServeModel.for_minicpm_sala(None, cfg)
    pages = place(jax.eval_shape(lambda: init_page_leaves(
        cfg.n_layer, pool, block, model.page_leaves, cfg.compute_dtype,
        state=(cfg.lightning_layers, slots, model.state_leaves))))
    assert [sorted(p) for p in pages] == [["ck", "k", "v"]] \
        + [["state"]] * 6 + [["ck", "k", "v"]]
    assert pages[1]["state"].shape == (slots, 32, 128, 128)
    assert pages[0]["k"].shape == (pool, block, 1, 256)
    assert pages[0]["ck"].shape == (pool, 1, 1, 256)
    params = place(jax.eval_shape(
        lambda: minicpm_sala_init(jax.random.key(0), cfg)))
    n_params = sum(x.size for x in jax.tree.leaves(params)
                   if x.dtype == jnp.bfloat16)
    assert round(n_params / 1e5) == 28205                     # 5.64 GB

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(params, pages, toks, tables, owned, pos):
        valid = jnp.arange(s_len)[None, :] < jnp.maximum(pos[:, None], 1)
        logits, pages, st = minicpm_sala_decode_paged(
            params, toks, cfg, pages, tables, owned,
            pos if kind == "decode_tick" else jnp.zeros_like(pos), valid,
            True, None if kind == "decode_tick" else pos[0])
        tail = jnp.stack([st[k] for k in SALA_COUNTERS])
        return (jnp.argmax(logits[:, -1], -1), tail), pages

    t0 = time.monotonic()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pages, i32(b, s_len), i32(b, per_seq), i32(b),
        i32(b)).compile()
    secs = time.monotonic() - t0
    text = compiled.as_text()
    assert "input_output_alias" in text
    for leaf in (pages[1]["state"], pages[0]["k"], pages[0]["ck"]):
        assert not pool_leaf_copies(text, leaf)
    held = re.sub(r"(?ms)^%?fused_computation[^\n]*\{\n.*?^\}\n", "", text)
    assert "fusion(" in held and len(held) < len(text)
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]", held):
        dims = [int(d) for d in m[2].split(",")]
        size = 1
        for d in dims:
            size *= d
        # [heads, S, S] scores, whole or a kv head's group of them
        assert size < 16 * 16384 * 16384, m[0]
    decode = kind == "decode_tick"
    if decode:
        # the walk's operands: lists of 128 runs (dense_len / 64) a (row, kv
        # head), and both leaves by runs of four pages
        for call in re.findall(r"%paged_attn(?:\.\d+)? = [^\n]*custom-call"
                               r"\(([^\n]*?)\), custom_call_target", text):
            made = [re.search(r"%s = (\S+) (\w+)\(" % re.escape(name), text)
                    for name in call.split(", ")]
            assert [m[1].split("{")[0] for m in made] == [
                "s32[128]", "s32[16384]", "bf16[128,32,256]",
                "bf16[20480,64,256]", "bf16[20480,64,256]"], made
            assert [m[2] for m in made[3:]] == ["bitcast", "bitcast"], made
    for name, n in (("lightning_step", 6 * decode),
                    ("lightning_chunk", 6 * (not decode)),
                    ("paged_attn", 2 * decode),
                    ("flash_gqa_fwd", 2 * (not decode))):
        calls = re.findall(r"%%%s(?:\.\d+)? = [^\n]*custom-call" % name, text)
        assert len(calls) == n, (name, len(calls))
    for scope in ("attn/gate", "lightning/out_norm", "sparse/compress",
                  "sparse/select", "sparse_attn",
                  "lightning/step" if decode else "lightning/chunk") \
            + (() if decode else ("dense_attn",)):
        assert re.search(r'op_name="[^"]*/%s/' % scope, text), scope
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"[minicpm_sala {kind}] compiled in {secs:.0f} s: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB, live {live / 1e9:.2f} GB")
    assert 9.1e9 < mem.argument_size_in_bytes < 9.4e9
    assert mem.alias_size_in_bytes > 3.5e9            # state and pool
    assert live < 15.75 * 2 ** 30 - 0.5e9, live               # the chip's HBM
    if decode:
        # a copy of the state leaves would be 0.81 GB of temporaries, of a
        # layer's keys or values 0.67 GB
        assert mem.temp_size_in_bytes < 0.4e9, mem.temp_size_in_bytes
    else:
        assert not re.search(r"f32\[1,%d,73448\]" % s_len, text)
        assert mem.temp_size_in_bytes < 5.0e9
        assert '"estimated_cycles":"9223372036854775807"' not in text


@pytest.mark.parametrize("kind", ["decode_tick", "prefill_4096",
                                  "prefill_4096_fresh"])
def test_mhc_and_latent_serving_programs_at_the_published_shapes(
        one_chip, kind, monkeypatch, capsys):
    """Xing4.0-29B-A4B as ``serve.xing4.0-29b-a4b.backlog-4k-in`` runs it
    (the cut configuration file: one dense and five expert layers, all 64
    experts of each, the whole vocabulary; 64 slots, 18,432 latent pages),
    donated. Both programs hold the mix's two kernels once a sublayer
    (``mhc_pre``, ``mhc_post``: 12 each) and ``moe_gmm``; the decode tick
    holds ``mla_paged_attn`` once a layer; none copies the latent pool or an
    expert bank, and NO buffer of the stream's size is a copy, a relayout
    or a float32 image of it: the stream is ``[rows, 4 x 3584]`` bfloat16,
    rewritten in place by ``mhc_post``. The 4,096-token prefill keeps one
    position's logits and fits the chip beside 11.9 GB of weights and
    cache; live bytes are printed. Dispatched ``fresh`` (from position 0, as
    every prefill of the cell is) it attends its own rows through
    ``latent_prefill`` once a layer, gathers no page and holds no ``[32,
    chunk, 4096]`` float32 scores; past 0 it keeps the gather and the
    chunked walk."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.models.xing import (
        XING_COUNTERS, XingConfig, xing_decode_paged, xing_init,
    )
    from distributed_lion_tpu.serve.engine import ServeModel
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = XingConfig.named(os.path.join(
        root, "benchmark", "configs", "xing4.0-29b-a4b.json"))
    block, per_seq, slots, pool = 16, 288, 64, 18432
    decode, fresh = kind == "decode_tick", kind.endswith("_fresh")
    b, s_len = (slots, 1) if decode else (1, int(kind.split("_")[1]))

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    model = ServeModel.for_xing(None, cfg)
    pages = place(jax.eval_shape(lambda: init_page_leaves(
        cfg.n_layer, pool, block, model.page_leaves, cfg.compute_dtype)))
    leaf = pages[0]["kv"]
    assert leaf.shape == (pool, block, 1, 640) and len(pages) == 6
    params = place(jax.eval_shape(lambda: xing_init(jax.random.key(0), cfg)))
    matrices = sum(x.size for x in jax.tree.leaves(params)
                   if x.ndim > 1 and x.shape[-1] != 128)
    assert round(matrices / 1e5) == 47885                     # 9.58 GB

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(params, pages, toks, tables, pos):
        valid = jnp.arange(s_len)[None, :] < jnp.maximum(pos[:, None], 1)
        logits, pages, st = xing_decode_paged(
            params, toks, cfg, pages, tables,
            pos if decode else jnp.zeros_like(pos), valid, True,
            None if decode else pos[0], fresh)
        tail = jnp.stack([st[k] for k in XING_COUNTERS])
        return (jnp.argmax(logits[:, -1], -1), tail), pages

    t0 = time.monotonic()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pages, i32(b, s_len), i32(b, per_seq), i32(b)).compile()
    secs = time.monotonic() - t0
    text = compiled.as_text()
    assert "input_output_alias" in text
    assert not pool_leaf_copies(text, leaf)
    bank = jax.ShapeDtypeStruct((cfg.n_experts, cfg.d_model, cfg.moe_d_ff),
                                jnp.bfloat16)
    assert not pool_leaf_copies(text, bank)
    for name in ("mhc_pre", "mhc_post"):
        calls = re.findall(r"%%%s(?:\.\d+)? = [^\n]*custom-call" % name, text)
        assert len(calls) == 2 * cfg.n_layer == 12, (name, len(calls))
    assert _named_custom_call(text, "moe_gmm")
    calls = re.findall(r"%mla_paged_attn(?:\.\d+)? = [^\n]*custom-call",
                       text)
    assert len(calls) == cfg.n_layer * decode
    calls = re.findall(r"%latent_prefill(?:\.\d+)? = [^\n]*custom-call", text)
    assert len(calls) == cfg.n_layer * fresh
    if not decode:     # the walk's scores and its gather go with ``fresh``
        from distributed_lion_tpu.ops.attention import query_chunk

        scores = "f32[32,%d,4096]" % query_chunk(1, cfg.n_head, s_len, s_len)
        assert (scores in text) != fresh, scores
        assert bool(re.search(r'op_name="[^"]*/paged_gather/', text)) != fresh
    # the stream: no buffer of its size is a copy, a relayout or a float32
    # image of it (a ``[rows, 4, 3584]`` buffer is the expert layer's
    # combine over top_k = 4 picks, not the stream)
    rows, wide = b * s_len, cfg.hc_mult * cfg.d_model
    held = re.sub(r"(?ms)^%?fused_computation[^\n]*\{\n.*?^\}\n", "", text)
    assert not re.search(r"= f32\[%d,%d\]" % (rows, wide), held)
    assert not re.search(r"= bf16\[%d,%d\]\S* (copy|transpose)\("
                         % (rows, wide), held)
    for scope in ("mhc/pre", "mhc/post", "mhc/read_out", "mla/q",
                  "mla/kv_latent", "moe/route", "moe/sort", "moe/experts",
                  "moe/shared", "moe/combine"):
        assert re.search(r'op_name="[^"]*/%s/' % scope, text), scope
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    with capsys.disabled():
        print(f"\n[mhc_and_latent] {kind}: compiled in {secs:.0f} s; "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB, live "
              f"{live / 1e9:.2f} GB")
    assert 11.8e9 < mem.argument_size_in_bytes < 12.0e9
    assert mem.alias_size_in_bytes > 2.2e9                    # the pool
    assert live < 15.75 * 2 ** 30 - 0.5e9, live               # the chip's HBM
    if decode:
        assert mem.temp_size_in_bytes < 0.2e9, mem.temp_size_in_bytes
    else:
        assert not re.search(r"f32\[1,%d,131072\]" % s_len, text)
        assert mem.temp_size_in_bytes < 2.5e9, mem.temp_size_in_bytes
        assert '"estimated_cycles":"9223372036854775807"' not in text


@pytest.mark.parametrize("kind", ["decode_tick", "prefill_12288"])
def test_dots3_serving_programs_at_the_published_shapes(
        one_chip, kind, monkeypatch):
    """dots3-note as ``serve.dots3-note-prev.backlog-12k`` runs it (the cut
    configuration file: the leading dense full layer and one period sliding
    x 3, full; 32 of 256 experts, an eighth of the vocabulary; 64 slots,
    65,536 pages of latent rows and index keys in two full layers, three
    rings of 34 pages a slot), donated. The decode tick holds ``dsa_index``
    and ``dsa_attn`` once a full layer (their page operands the pool leaves
    seen as 16,384 runs of 64 rows, a bitcast) and ``window_mla_attn`` once a
    sliding layer, writes pages and rings IN PLACE and selects in XLA. The
    12,288-token prefill (the top bucket) takes its masks a chunk of queries
    at a time and runs the tiled kernel ``dsa_prefill`` once a full layer: no
    ``[128, 12288, 12288]`` buffer outside a fused body, one position's
    logits, and it fits the chip beside 11.6 GB of weights and cache. Prints
    both programs' live bytes."""
    from distributed_lion_tpu.analysis.serve_check import pool_leaf_copies
    from distributed_lion_tpu.models.dots3 import (
        DOTS3_COUNTERS, Dots3Config, dots3_decode_paged, dots3_init,
    )
    from distributed_lion_tpu.ops.attention import ring_pages
    from distributed_lion_tpu.serve.engine import ServeModel
    from distributed_lion_tpu.serve.kv_cache import init_page_leaves

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = Dots3Config.named(os.path.join(
        root, "benchmark", "configs", "dots3-note-prev.json"))
    assert cfg.held == (0, 32) and cfg.windowed == (
        False, True, True, True, False)
    block, per_seq, slots, pool = 16, 1024, 64, 65536
    b, s_len = (slots, 1) if kind == "decode_tick" \
        else (1, int(kind.split("_")[1]))

    def place(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    model = ServeModel.for_dots3(None, cfg)
    ring = slots * ring_pages(cfg.window, block)
    pages = place(jax.eval_shape(lambda: init_page_leaves(
        cfg.n_layer, pool, block, model.page_leaves, cfg.compute_dtype,
        ring=(cfg.window_layers, ring, model.window_leaves))))
    assert [sorted(p) for p in pages] == [["ik", "kv"]] + [["kv"]] * 3 \
        + [["ik", "kv"]]
    assert pages[0]["kv"].shape == (pool, block, 1, 640)
    assert pages[0]["ik"].shape == (pool, block, 1, 128)
    assert pages[1]["kv"].shape == (slots * 34, block, 1, 1152)
    params = place(jax.eval_shape(lambda: dots3_init(jax.random.key(0), cfg)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert round(n_params / 1e6) == 4087                       # 8.17 GB

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def fn(params, pages, toks, tables, owned, pos):
        valid = jnp.arange(s_len)[None, :] < jnp.maximum(pos[:, None], 1)
        logits, pages, st = dots3_decode_paged(
            params, toks, cfg, pages, tables, owned,
            pos if kind == "decode_tick" else jnp.zeros_like(pos), valid,
            True, None if kind == "decode_tick" else pos[0])
        tail = jnp.stack([st[k] for k in DOTS3_COUNTERS])
        return (jnp.argmax(logits[:, -1], -1), tail), pages

    t0 = time.monotonic()
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pages, i32(b, s_len), i32(b, per_seq), i32(b),
        i32(b)).compile()
    secs = time.monotonic() - t0
    text = compiled.as_text()
    assert "input_output_alias" in text
    for leaf in (pages[0]["kv"], pages[0]["ik"], pages[1]["kv"]):
        assert not pool_leaf_copies(text, leaf)
    held = re.sub(r"(?ms)^%?fused_computation[^\n]*\{\n.*?^\}\n", "", text)
    assert "fusion(" in held and len(held) < len(text)
    for m in re.finditer(r"= (\w+)\[([\d,]+)\]", held):
        size = 1
        for d in m[2].split(","):
            size *= int(d)
        assert size < 16 * 12288 * 12288, m[0]
    decode = kind == "decode_tick"
    for name, n in (("dsa_index", 2 * decode), ("dsa_attn", 2 * decode),
                    ("window_mla_attn", 3 * decode),
                    ("dsa_prefill", 2 * (not decode)),
                    ("mla_paged_attn", 0)):
        calls = re.findall(r"%%%s(?:\.\d+)? = [^\n]*custom-call" % name, text)
        assert len(calls) == n, (name, len(calls))
    if decode:
        # the full layers' walks by runs of four pages: both leaves a bitcast
        for name, lanes in (("dsa_index", 128), ("dsa_attn", 640)):
            for call in re.findall(
                    r"%%%s(?:\.\d+)? = [^\n]*custom-call\(([^\n]*?)\), "
                    r"custom_call_target" % name, text):
                last = call.split(", ")[-1]
                made = re.search(r"%s = (\S+) (\w+)\(" % re.escape(last), text)
                assert made[1].split("{")[0] == "bf16[16384,64,%d]" % lanes
                assert made[2] == "bitcast", made[0]
    assert len(re.findall(r"%moe_gmm(?:\.\d+)? = [^\n]*custom-call", text)) \
        >= 4
    for scope in ("attn/gate", "dsa/index", "dsa/select", "dsa/attn",
                  "window_mla", "mla/q", "mla/kv_latent"):
        assert re.search(r'op_name="[^"]*/%s[/"]' % scope, text), scope
    mem = compiled.memory_analysis()
    live = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    print(f"[dots3 {kind}] compiled in {secs:.0f} s: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB, live {live / 1e9:.2f} GB")
    assert 11.5e9 < mem.argument_size_in_bytes < 11.8e9
    assert mem.alias_size_in_bytes > 3.4e9            # pages and rings
    assert live < 15.75 * 2 ** 30 - 0.5e9, live               # the chip's HBM
    if decode:
        assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
    else:
        assert not re.search(r"f32\[1,%d,19008\]" % s_len, text)
        assert '"estimated_cycles":"9223372036854775807"' not in text
