"""KV-cache decode and generation (SURVEY §4 unit style): the incremental
decode path must match the full forward position-for-position, and the
jitted scan generation must be deterministic under greedy sampling."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from distributed_lion_tpu.models.generate import generate, sample_logits
from distributed_lion_tpu.models.gpt2 import (
    GPT2Config, gpt2_apply, gpt2_decode, gpt2_init, gpt2_init_cache,
)
from distributed_lion_tpu.models.llama import (
    LlamaConfig, llama_apply, llama_decode, llama_init, llama_init_cache,
)


# Model calls run COMPILED, one program a shape (ISSUE 35): eagerly a forward
# pass is a few hundred one-op programs. ``pos`` is traced, as in generate.
_gpt2_apply = jax.jit(gpt2_apply, static_argnums=2)
_gpt2_decode = jax.jit(gpt2_decode, static_argnums=2)
_llama_apply = jax.jit(llama_apply, static_argnums=2)
_llama_decode = jax.jit(llama_decode, static_argnums=2)


def _tokens(vocab, b, t, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, vocab, (b, t)), jnp.int32
    )


def test_gpt2_decode_matches_apply():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(0), cfg)
    toks = _tokens(cfg.vocab_size, 2, 12)
    full = _gpt2_apply(params, toks, cfg)

    cache = gpt2_init_cache(cfg, 2, 16)
    # prefill with the first 8, then decode one token at a time
    pre, cache = _gpt2_decode(params, toks[:, :8], cfg, cache, 0)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :8]),
                               rtol=2e-2, atol=2e-2)
    for i in range(8, 12):
        step, cache = _gpt2_decode(params, toks[:, i:i + 1], cfg, cache, i)
        np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(full[:, i]),
                                   rtol=2e-2, atol=2e-2)


def test_llama_decode_matches_apply():
    cfg = LlamaConfig.tiny()  # GQA: 4 heads, 2 kv heads
    params = llama_init(jax.random.key(1), cfg)
    toks = _tokens(cfg.vocab_size, 2, 10)
    full = _llama_apply(params, toks, cfg)

    cache = llama_init_cache(cfg, 2, 12)
    pre, cache = _llama_decode(params, toks[:, :6], cfg, cache, 0)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :6]),
                               rtol=2e-2, atol=2e-2)
    for i in range(6, 10):
        step, cache = _llama_decode(params, toks[:, i:i + 1], cfg, cache, i)
        np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(full[:, i]),
                                   rtol=2e-2, atol=2e-2)


def test_generate_greedy_deterministic():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(2), cfg)
    prompt = _tokens(cfg.vocab_size, 2, 5, seed=3)
    decode = partial(_gpt2_decode_fn, cfg)
    init_cache = partial(gpt2_init_cache, cfg)

    out1 = generate(decode, init_cache, params, prompt, 8)
    out2 = generate(decode, init_cache, params, prompt, 8)
    assert out1.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert int(np.asarray(out1).max()) < cfg.vocab_size
    # first generated token == argmax of the full forward's last position
    full = _gpt2_apply(params, prompt, cfg)
    np.testing.assert_array_equal(
        np.asarray(out1[:, 0]), np.asarray(jnp.argmax(full[:, -1], -1))
    )


def test_generate_eos_pads():
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(2), cfg)
    prompt = _tokens(cfg.vocab_size, 2, 5, seed=3)
    decode = partial(_gpt2_decode_fn, cfg)
    init_cache = partial(gpt2_init_cache, cfg)
    greedy = np.asarray(generate(decode, init_cache, params, prompt, 8))
    # declare the first greedily-emitted token of row 0 to be EOS: everything
    # after it in that row must be pad (99)
    eos = int(greedy[0, 0])
    out = np.asarray(generate(decode, init_cache, params, prompt, 8,
                              eos_id=eos, pad_id=99))
    row = out[0]
    assert row[0] == eos and (row[1:] == 99).all()


def test_sample_logits_top_k_restricts_support():
    logits = jnp.asarray([[0.0, 5.0, 4.0, -1.0]])
    for seed in range(20):
        t = sample_logits(logits, jax.random.key(seed), temperature=1.0, top_k=2)
        assert int(t[0]) in (1, 2)
    assert int(sample_logits(logits, jax.random.key(0), temperature=0.0)[0]) == 1


def _gpt2_decode_fn(cfg, params, tokens, cache, pos):
    return gpt2_decode(params, tokens, cfg, cache, pos)


def test_generate_cli_smoke(capsys):
    from distributed_lion_tpu.cli.run_generate import main

    text = main(["--model_family", "gpt2", "--model_name", "tiny",
                 "--prompt", "ab", "--max_new_tokens", "4",
                 "--temperature", "0"])
    assert isinstance(text, str)
    assert "ab" in capsys.readouterr().out


def test_generate_cli_roundtrips_exported_model(tmp_path):
    """Train-export-generate cycle: a model saved with utils.serialization
    reloads byte-identically through the CLI path."""
    from distributed_lion_tpu.cli.run_generate import main
    from distributed_lion_tpu.utils.serialization import load_pytree, save_pytree

    cfg = GPT2Config.tiny(vocab_size=259)  # byte tokenizer id space
    params = gpt2_init(jax.random.key(7), cfg)
    path = tmp_path / "model.npz"
    save_pytree(path, params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(load_pytree(path))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    text = main(["--model_path", str(path), "--model_family", "gpt2",
                 "--model_name", "tiny", "--prompt", "hi",
                 "--max_new_tokens", "3", "--temperature", "0"])
    assert isinstance(text, str)


def test_top_p_nucleus_filtering():
    """top_p keeps exactly the smallest head-mass prefix: with probs
    (.5, .3, .15, .05) and top_p=.7 only tokens {0, 1} can be sampled;
    top_p→tiny degrades to greedy (the top token always survives)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_lion_tpu.models.generate import sample_logits

    probs = jnp.array([[0.5, 0.3, 0.15, 0.05]])
    logits = jnp.log(probs)
    draws = [int(sample_logits(logits, jax.random.key(s), 1.0, None, 0.7)[0])
             for s in range(64)]
    assert set(draws) <= {0, 1}, set(draws)
    assert len(set(draws)) == 2  # both survivors actually get sampled
    tiny = [int(sample_logits(logits, jax.random.key(s), 1.0, None, 1e-6)[0])
            for s in range(8)]
    assert set(tiny) == {0}
    # top_p=1.0 keeps everything: all four ids reachable
    full = [int(sample_logits(logits, jax.random.key(s), 1.0, None, 1.0)[0])
            for s in range(200)]
    assert set(full) == {0, 1, 2, 3}, set(full)


def test_sample_logits_top_k_ge_vocab_keeps_everything():
    """top_k >= vocab filters nothing: the draw is bit-identical to the
    unfiltered draw under the same key (load-bearing once the serving
    engine samples per-tick with caller-provided top_k)."""
    logits = jnp.log(jnp.array([[0.5, 0.3, 0.15, 0.05]]))
    for s in range(12):
        key = jax.random.key(s)
        plain = int(sample_logits(logits, key, 1.0)[0])
        assert int(sample_logits(logits, key, 1.0, top_k=4)[0]) == plain
        assert int(sample_logits(logits, key, 1.0, top_k=400)[0]) == plain


def test_sample_logits_top_p_one_keeps_everything():
    """top_p=1.0 keeps the full support (exclusive-cumulative mass before
    the last token is < 1.0): bit-identical to the unfiltered draw."""
    logits = jnp.log(jnp.array([[0.5, 0.3, 0.15, 0.05]]))
    for s in range(12):
        key = jax.random.key(s)
        assert int(sample_logits(logits, key, 1.0, None, 1.0)[0]) == \
            int(sample_logits(logits, key, 1.0)[0])


def test_generate_pad_id_equals_eos_id():
    """pad_id == eos_id must not re-trigger/flicker the finished mask:
    after the first EOS the row is eos forever (the pad IS eos), and the
    mask never un-finishes."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(2), cfg)
    prompt = _tokens(cfg.vocab_size, 2, 5, seed=3)
    decode = partial(_gpt2_decode_fn, cfg)
    init_cache = partial(gpt2_init_cache, cfg)
    greedy = np.asarray(generate(decode, init_cache, params, prompt, 8))
    eos = int(greedy[0, 0])
    out = np.asarray(generate(decode, init_cache, params, prompt, 8,
                              eos_id=eos, pad_id=eos))
    assert (out[0] == eos).all(), out[0]


def test_generate_max_new_tokens_1():
    """max_new_tokens=1 is a zero-length scan: shape [B, 1] and the one
    token equals the prefill logits' argmax."""
    cfg = GPT2Config.tiny()
    params = gpt2_init(jax.random.key(2), cfg)
    prompt = _tokens(cfg.vocab_size, 2, 5, seed=3)
    decode = partial(_gpt2_decode_fn, cfg)
    init_cache = partial(gpt2_init_cache, cfg)
    out = np.asarray(generate(decode, init_cache, params, prompt, 1))
    assert out.shape == (2, 1)
    full = _gpt2_apply(params, prompt, cfg)
    np.testing.assert_array_equal(out[:, 0],
                                  np.asarray(jnp.argmax(full[:, -1], -1)))


def test_batched_left_padded_generate_matches_solo():
    """ISSUE 9 satellite: variable-length prompts batch into one
    left-padded generate call (per-row position offsets + pad masking) and
    each row generates exactly what a solo run of its prompt does — for
    BOTH families (llama exercises per-row rotary gathers)."""
    from distributed_lion_tpu.models.llama import (
        llama_decode, llama_init, llama_init_cache,
    )

    cases = [
        ("gpt2", GPT2Config.tiny(), gpt2_init,
         lambda cfg: (lambda p, t, c, pos, off=None:
                      gpt2_decode(p, t, cfg, c, pos, off)),
         gpt2_init_cache),
        ("llama", LlamaConfig.tiny(), llama_init,
         lambda cfg: (lambda p, t, c, pos, off=None:
                      llama_decode(p, t, cfg, c, pos, off)),
         llama_init_cache),
    ]
    rng = np.random.default_rng(1)
    for fam, cfg, init, mk_dec, init_cache in cases:
        params = init(jax.random.key(2), cfg)
        dec = mk_dec(cfg)
        ic = partial(init_cache, cfg)
        prompts = [list(map(int, rng.integers(1, cfg.vocab_size, n)))
                   for n in (3, 7, 5)]
        T = max(len(p) for p in prompts)
        batch = np.zeros((len(prompts), T), np.int32)
        for i, p in enumerate(prompts):
            batch[i, T - len(p):] = p  # left-pad: real tokens right-aligned
        lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
        out = np.asarray(generate(dec, ic, params, jnp.asarray(batch), 6,
                                  prompt_lens=lens))
        for i, p in enumerate(prompts):
            solo = np.asarray(generate(dec, ic, params,
                                       jnp.asarray([p], jnp.int32), 6))
            np.testing.assert_array_equal(out[i], solo[0], err_msg=f"{fam}:{i}")


def test_generate_cli_multi_prompt(tmp_path, capsys):
    """run_generate batches several --prompt values (and --prompt_file
    lines) through ONE left-padded generate call; per-prompt output lines
    match the single-prompt invocations."""
    from distributed_lion_tpu.cli.run_generate import main

    pf = tmp_path / "prompts.txt"
    pf.write_text("hello\n\nworld\n")
    texts = main(["--model_family", "gpt2", "--model_name", "tiny",
                  "--prompt", "ab", "cdef", "--prompt_file", str(pf),
                  "--max_new_tokens", "4", "--temperature", "0"])
    assert isinstance(texts, list) and len(texts) == 4
    capsys.readouterr()
    for prompt, text in zip(("ab", "cdef", "hello", "world"), texts):
        solo = main(["--model_family", "gpt2", "--model_name", "tiny",
                     "--prompt", prompt, "--max_new_tokens", "4",
                     "--temperature", "0"])
        assert solo == text, prompt
    # --prompt_file ALONE must serve exactly the file's prompts — no
    # default "Hello" sneaking into the batch
    only_file = main(["--model_family", "gpt2", "--model_name", "tiny",
                      "--prompt_file", str(pf), "--max_new_tokens", "4",
                      "--temperature", "0"])
    assert isinstance(only_file, list) and len(only_file) == 2
    assert only_file == texts[2:]


def test_top_p_degenerate_values_fall_back_to_greedy():
    import jax
    import jax.numpy as jnp

    from distributed_lion_tpu.models.generate import sample_logits

    logits = jnp.log(jnp.array([[0.5, 0.3, 0.15, 0.05]]))
    for s in range(8):
        assert int(sample_logits(logits, jax.random.key(s), 1.0,
                                 None, 0.0)[0]) == 0
        assert int(sample_logits(logits, jax.random.key(s), 1.0,
                                 0, None)[0]) == 0
