"""Continuous batching: slot isolation, scheduling, and parity with
isolated per-request decoding."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.runtime.batcher import ContinuousBatcher, Request


@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("llama3.2-1b-smoke")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _isolated_decode(model, params, prompt, max_new, max_len):
    """Reference: one request alone in a batch-1 loop."""
    cache = model.make_cache(1, max_len, mode="init", dtype=jnp.float32)
    out = []
    pos = 0
    tok = None
    for t in prompt:
        logits, cache = model.decode_step(
            params, jnp.asarray([[t]], jnp.int32), cache, pos
        )
        pos += 1
    for _ in range(max_new):
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        logits, cache = model.decode_step(
            params, jnp.asarray([[tok]], jnp.int32), cache, pos
        )
        pos += 1
    return out


@pytest.mark.slow  # decodes a full batch twice
def test_batched_matches_isolated(model_and_params):
    cfg, model, params = model_and_params
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (3, 5, 4)]
    max_new = 4

    batcher = ContinuousBatcher(model, params, batch_slots=2, max_len=24)
    for i, p in enumerate(prompts):
        batcher.submit(Request(rid=i, prompt=p, max_new=max_new))
    finished = batcher.run_to_completion()
    assert set(finished) == {0, 1, 2}

    for i, p in enumerate(prompts):
        want = _isolated_decode(model, params, p, max_new, 24)
        got = finished[i].output
        assert got == want, f"req {i}: batched {got} != isolated {want}"


def test_more_requests_than_slots(model_and_params):
    cfg, model, params = model_and_params
    rng = np.random.default_rng(1)
    batcher = ContinuousBatcher(model, params, batch_slots=2, max_len=16)
    for i in range(5):
        batcher.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab, 2).astype(np.int32),
                               max_new=2))
    finished = batcher.run_to_completion()
    assert len(finished) == 5
    assert all(len(r.output) == 2 for r in finished.values())


def test_vector_index_decode_matches_scalar(model_and_params):
    """The per-slot index path must equal the scalar path when positions
    coincide (the enabling primitive for continuous batching)."""
    cfg, model, params = model_and_params
    B, S = 2, 6
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    c1 = model.make_cache(B, 8, mode="init", dtype=jnp.float32)
    c2 = model.make_cache(B, 8, mode="init", dtype=jnp.float32)
    for t in range(S):
        l1, c1 = model.decode_step(params, toks[:, t:t+1], c1, t)
        l2, c2 = model.decode_step(params, toks[:, t:t+1], c2,
                                   jnp.full((B,), t, jnp.int32))
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-5)


def test_dense_overlong_prompt_truncates_like_paged(model_and_params):
    """A prompt longer than the dense rectangle ends "truncated" with no
    output when it fills the rectangle, as it does on the paged backend,
    instead of feeding rows past max_len."""
    from repro.runtime.lifecycle import FinishReason

    cfg, model, params = model_and_params
    prompt = _prompts(cfg, (12,), seed=4)[0]
    for kw in (dict(), dict(paged=True, page_size=4)):
        b = ContinuousBatcher(model, params, batch_slots=1, max_len=8, **kw)
        b.submit(Request(rid=0, prompt=prompt, max_new=4))
        req = b.run_to_completion()[0]
        assert (req.finish_reason, req.output) == (FinishReason.TRUNCATED,
                                                   []), kw
        assert b.steps_run == 8, kw


# ---------------------------------------------------------------------------
# the plain step's program inputs, pinned
# ---------------------------------------------------------------------------


def _digest(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        if isinstance(p, str):
            h.update(p.encode())
        else:
            a = np.asarray(p)
            h.update(f"{a.dtype}{a.shape}".encode())
            h.update(a.tobytes())
    return h.hexdigest()[:16]


def _prompts(cfg, lengths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lengths]


def _submit_all(b, cfg, lengths=(3, 9, 5, 6), max_new=4, seed=5):
    for i, p in enumerate(_prompts(cfg, lengths, seed)):
        b.submit(Request(rid=i, prompt=p, max_new=max_new))
    b.run_to_completion()
    return b


def _token_stepping(cfg, model, params):
    return _submit_all(_Recorded(model, params, batch_slots=2,
                                 max_len=24, paged=True, page_size=4), cfg)


def _chunked(cfg, model, params):
    return _submit_all(_Recorded(model, params, batch_slots=2,
                                 max_len=24, paged=True, page_size=4,
                                 prefill_chunk=4), cfg)


def _prefix_cow(cfg, model, params):
    # 14-token prompts sharing their first 10: a prefix hit ends inside a
    # page, so admission mounts it copy-on-write
    b = _Recorded(model, params, batch_slots=2, max_len=20, paged=True,
                  page_size=4, num_pages=40, prefix_cache=True,
                  prefill_chunk=4)
    rng = np.random.default_rng(2)
    common = rng.integers(0, cfg.vocab, 10)
    for i in range(4):
        tail = rng.integers(0, cfg.vocab, 4)
        b.submit(Request(rid=i, prompt=np.concatenate([common, tail])
                         .astype(np.int32), max_new=4))
    b.run_to_completion()
    assert b.cow_copies >= 1
    return b


def _preemption(cfg, model, params):
    # two reservations of 2 pages do not fit in 3: the later, higher-
    # priority request preempts the first, which resumes afterwards
    b = _Recorded(model, params, batch_slots=2, max_len=12, paged=True,
                  page_size=4, num_pages=3, prefix_cache=True)
    pa, pb = _prompts(cfg, (4, 4), seed=7)
    ra = Request(rid=0, prompt=pa, max_new=4, priority=0)
    b.submit(ra)
    while len(ra.output) < 1:
        b.step()
    b.submit(Request(rid=1, prompt=pb, max_new=4, priority=1))
    b.run_to_completion()
    assert b.preemptions_total == 1 and b.resumes_total == 1
    return b


def _chaos_quarantine(cfg, model, params):
    from repro.runtime.lifecycle import (
        ChaosConfig, ChaosInjector, FinishReason,
    )

    chaos = ChaosInjector(ChaosConfig(seed=3, poison_at_steps=(3,),
                                      fail_at_steps=(2,),
                                      bitflip_at_steps=(1, 4)))
    b = _submit_all(_Recorded(model, params, batch_slots=2, max_len=16,
                              paged=True, page_size=4, chaos=chaos,
                              nonfinite_guard=True, abft=True),
                    cfg, lengths=(2, 3, 4))
    assert sum(r.finish_reason == FinishReason.FAILED
               for r in b.finished.values()) == 1
    assert b.sdc_detected == b.sdc_corrected == chaos.bitflips_injected == 2
    return b


def _disagg(cfg, model, params):
    from repro.runtime.disagg import DisaggEngine

    eng = DisaggEngine(model, params, prefill_workers=2, batch_slots=2,
                       max_len=24, page_size=4, prefill_chunk=4)
    record = _record(eng.batcher)
    for i, p in enumerate(_prompts(cfg, (12, 8, 1, 17), seed=0)):
        eng.submit(Request(rid=i, prompt=p, max_new=3))
    eng.run_to_completion()
    eng.batcher.recorded = record
    return eng.batcher


def _dense(cfg, model, params):
    return _submit_all(_Recorded(model, params, batch_slots=2,
                                 max_len=24), cfg)


def _record(b):
    """Wraps the batcher's decode program: each call's table width (0 on
    the dense backend) and a digest of its token, index, table and length
    arrays."""
    calls = []
    inner = b._step

    def step(params, token, cache, index, *paged):
        calls.append((paged[0].shape[1] if paged else 0,
                      _digest(token, index, *paged)))
        return inner(params, token, cache, index, *paged)

    b._step = step
    return calls


class _Recorded(ContinuousBatcher):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.recorded = _record(self)


# what each run fed the decode program and served at the commit before the
# plain step and the speculative window became one step method
_PLAIN_STEP_BEFORE = {
    "token_stepping": {
        "widths": [1, 1, 1, 1, 2, 2, 2, 2, 4, 4, 4, 4, 2, 2, 1, 1, 2, 2, 2, 2,
                   4],
        "calls": "a01e3befcb22d562", "health": "48f90f3941fdb0ea",
        "streams": {0: [65, 117, 101, 190], 1: [35, 35, 35, 35],
                    2: [206, 206, 235, 32], 3: [21, 206, 247, 155]}},
    "chunked": {
        "widths": [4, 4, 4, 4, 2, 2, 2, 4],
        "calls": "f11dd0b3f379c522", "health": "7db25b8e44c70624",
        "streams": {0: [65, 117, 101, 190], 1: [35, 35, 35, 35],
                    2: [206, 206, 235, 32], 3: [21, 206, 247, 155]}},
    "prefix_cow": {
        "widths": [4, 4, 4, 5, 4, 4, 4, 5],
        "calls": "8ee53878d1046d8e", "health": "521d5c86ec6ee8f8",
        "streams": {0: [198, 198, 198, 198], 1: [33, 35, 235, 245],
                    2: [194, 194, 194, 194], 3: [32, 198, 198, 198]}},
    "preemption": {
        "widths": [1, 1, 1, 1, 1, 1, 1, 1, 2, 2, 2, 2, 2, 2],
        "calls": "e50dbb7fb2f1a8ef", "health": "6f7c28d3e7ded26e",
        "streams": {0: [101, 92, 61, 255], 1: [157, 122, 157, 157]}},
    "chaos_quarantine": {
        "widths": [1, 1, 1, 1, 2, 1, 1, 1, 2, 2, 2],
        "calls": "f5885b07ae7b7565", "health": "a9e7a4d496b11e4e",
        "streams": {0: [190, 190, 190, 190], 1: [173], 2: [68, 213, 68, 213]}},
    "disagg": {
        "widths": [2, 4, 4, 4, 6, 6, 6],
        "calls": "62b00d333134cc93", "health": "495baf401956b823",
        "streams": {0: [134, 50, 109], 1: [17, 17, 206], 2: [157, 23, 23],
                    3: [227, 44, 245]}},
    "dense": {
        "widths": [0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0,
                   0],
        "calls": "65add64de6bd32df", "health": "0a17b5827cbe2c60",
        "streams": {0: [65, 117, 101, 190], 1: [35, 35, 35, 35],
                    2: [206, 206, 235, 32], 3: [21, 206, 247, 155]}},
}


@pytest.mark.parametrize("run", [
    _token_stepping, _chunked, _prefix_cow, _preemption, _chaos_quarantine,
    _disagg, _dense,
], ids=lambda f: f.__name__.strip("_"))
def test_plain_step_feeds_the_decode_program_as_before(model_and_params,
                                                       run):
    """Every call of the decode program gets the same table width and the
    same arrays as before, and every run serves the same tokens with the
    same per-step counts."""
    cfg, model, params = model_and_params
    b = run(cfg, model, params)
    widths = [w for w, _ in b.recorded]
    got = {
        "widths": widths,
        "calls": _digest(*(d for _, d in b.recorded)),
        "health": _digest(repr([(h.admitted, h.prefill_chunks, h.emitted,
                                 h.width, h.attn_pages) for h in b.health])),
        "streams": {k: list(r.output) for k, r in sorted(b.finished.items())},
    }
    assert got == _PLAIN_STEP_BEFORE[run.__name__.strip("_")]
