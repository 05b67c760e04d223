"""Sampling/inference tests (reference: examples/GPT2/predict_fns.py +
models/gpt2/sample.py — past-cache incremental decode with temperature /
top-k / multinomial). The KV-cache decode must match the full forward
exactly; the sampler's knobs must behave."""

import jax
import jax.numpy as jnp
import numpy as np

from tepdist_tpu.models import gpt2, sampling

CFG = gpt2.CONFIGS["test"]


def _params():
    return gpt2.init_params(CFG, jax.random.PRNGKey(0))


def _prompt(b=2, t=8, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0,
                              CFG.vocab_size)


def test_greedy_decode_matches_full_forward():
    """Incremental KV-cache decode == argmax over the full forward at
    every step (the cache path computes the same attention)."""
    params, prompt = _params(), _prompt()
    out = jax.jit(lambda p, t: sampling.sample(
        p, t, CFG, max_new_tokens=6, greedy=True))(params, prompt)
    toks = np.asarray(prompt)
    forward = jax.jit(lambda p, t: gpt2.forward(p, t, CFG))
    for _ in range(6):
        logits = forward(params, jnp.asarray(toks))
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1)).astype(np.int32)
        toks = np.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), toks)


def test_single_token_and_shapes():
    params, prompt = _params(), _prompt()
    out = sampling.sample(params, prompt, CFG, max_new_tokens=1,
                          greedy=True)
    assert out.shape == (2, 9)
    np.testing.assert_array_equal(np.asarray(out[:, :8]),
                                  np.asarray(prompt))


def test_topk_restricts_support():
    """With top_k=1 the multinomial draw IS the greedy choice regardless
    of temperature/key."""
    params, prompt = _params(), _prompt()
    g = sampling.sample(params, prompt, CFG, max_new_tokens=5, greedy=True)
    k1 = sampling.sample(params, prompt, CFG, max_new_tokens=5,
                         temperature=5.0, top_k=1,
                         key=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(k1))


def test_sampling_is_key_deterministic():
    params, prompt = _params(), _prompt()
    a = sampling.sample(params, prompt, CFG, max_new_tokens=5,
                        temperature=1.0, key=jax.random.PRNGKey(3))
    b = sampling.sample(params, prompt, CFG, max_new_tokens=5,
                        temperature=1.0, key=jax.random.PRNGKey(3))
    c = sampling.sample(params, prompt, CFG, max_new_tokens=5,
                        temperature=1.0, key=jax.random.PRNGKey(4))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_context_length_guard():
    params, prompt = _params(), _prompt(t=60)
    try:
        sampling.sample(params, prompt, CFG, max_new_tokens=10,
                        greedy=True)
    except ValueError as e:
        assert "n_ctx" in str(e)
    else:
        raise AssertionError("expected ValueError past n_ctx")


# -- slot-based batched serving cache (tepdist_tpu/serving/kv_cache.py) ----

def _serve_prompts(sizes, seed=5):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab_size, size=t).astype(np.int32)
            for t in sizes]


def _sequential_reference(params, prompt, max_new, **kw):
    """One B=1 sample() call — the ground truth the batched path must
    reproduce token-for-token."""
    out = sampling.sample(params, prompt[None], CFG,
                          max_new_tokens=max_new, **kw)
    return np.asarray(out)[0, len(prompt):]


def test_slot_batched_greedy_matches_sequential_sample():
    """Greedy outputs from the slot-based batched cache path are
    bit-identical to N sequential sample() calls — INCLUDING mid-stream
    slot reuse: 2 slots, 4 requests of mixed prompt/output lengths, so
    the short sequences retire early and later requests are admitted
    into the reused slots while the long ones are mid-decode."""
    from tepdist_tpu.serving import ServingEngine

    params = _params()
    prompts = _serve_prompts((5, 8, 3, 12))
    mnts = [6, 2, 9, 4]       # r1 retires after 2 tokens -> slot reused
    eng = ServingEngine(params, CFG, slots=2, max_len=32)
    for i, (p, m) in enumerate(zip(prompts, mnts)):
        assert eng.submit(f"r{i}", p,
                          max_new_tokens=m)["status"] == "queued"
    eng.run_until_idle()
    res = {r["request_id"]: r for r in eng.poll()}
    for i, (p, m) in enumerate(zip(prompts, mnts)):
        r = res[f"r{i}"]
        assert r["status"] == "done", r
        np.testing.assert_array_equal(
            np.asarray(r["tokens"], np.int32),
            _sequential_reference(params, p, m, greedy=True))


def test_slot_batched_seeded_sampling_matches_sample():
    """Non-greedy: the engine's per-request RNG split sequence mirrors
    sample()'s (seed s == sample(key=PRNGKey(s))), batched or not."""
    from tepdist_tpu.serving import ServingEngine

    params = _params()
    prompts = _serve_prompts((6, 4), seed=9)
    eng = ServingEngine(params, CFG, slots=2, max_len=32)
    for i, p in enumerate(prompts):
        eng.submit(f"s{i}", p, max_new_tokens=5, greedy=False,
                   temperature=1.0, seed=3 + i)
    eng.run_until_idle()
    res = {r["request_id"]: r for r in eng.poll()}
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            np.asarray(res[f"s{i}"]["tokens"], np.int32),
            _sequential_reference(params, p, 5, temperature=1.0,
                                  key=jax.random.PRNGKey(3 + i)))


def test_slot_pool_alloc_release():
    from tepdist_tpu.serving import SlotPool

    pool = SlotPool(2)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1} and pool.alloc() is None
    pool.release(a)
    assert pool.n_free == 1 and pool.alloc() == a
    pool.release(b)
    try:
        pool.release(b)
    except ValueError:
        pass
    else:
        raise AssertionError("double release must raise")


def test_prefill_bucketing_bounds_compiles():
    """Prompt lengths sharing a bucket share one compiled prefill; the
    bucket padding must not perturb the result (padded tail is causally
    masked)."""
    from tepdist_tpu.serving import ServableModel
    from tepdist_tpu.telemetry import metrics

    params = _params()
    model = ServableModel(params, CFG, slots=1, max_len=32)
    before = dict(metrics().snapshot()["counters"])
    seen = set()
    for p in _serve_prompts((5, 6, 7, 8), seed=2):   # all bucket<=8
        logits, _, _, bucket = model.prefill(p)
        seen.add(bucket)
        full = gpt2.forward(params, jnp.asarray(p[None]), CFG)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[0, -1]), rtol=1e-5,
                                   atol=1e-6)
    after = dict(metrics().snapshot()["counters"])
    assert seen == {8}
    assert after.get("serve_compiles", 0) - before.get(
        "serve_compiles", 0) == 1
