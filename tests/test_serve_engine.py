"""Serving engine: continuous batching correctness + prefix-cache hashing."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.serve import Request, ServeEngine

CFG = get_config("mistral_nemo_12b", smoke=True)


@pytest.fixture(scope="module")
def engine():
    api = build(CFG)
    params = api.init(jax.random.key(0))
    return api, params


def test_requests_complete(engine):
    api, params = engine
    eng = ServeEngine(api, params, n_slots=2, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, CFG.vocab_size, size=8).astype(np.int32),
                    max_new_tokens=6) for i in range(5)]
    eng.submit_all(reqs)
    for r in reqs:
        assert r.done
        assert len(r.out_tokens) == 6
        assert all(0 <= t < CFG.vocab_size for t in r.out_tokens)
    assert eng.stats["prefills"] == 5


def test_prefix_cache_hits(engine):
    api, params = engine
    eng = ServeEngine(api, params, n_slots=2, max_seq=64)
    prompt = np.arange(8, dtype=np.int32)
    reqs = [Request(i, prompt.copy(), max_new_tokens=4) for i in range(3)]
    eng.submit_all(reqs)
    assert eng.stats["prefix_hits"] == 2  # 2nd and 3rd identical prompts
    # identical prompts assigned in the SAME tick decode identically; the
    # 3rd joins later at a shifted lockstep position (documented engine
    # simplification), so only 0 and 1 are compared
    assert reqs[0].out_tokens == reqs[1].out_tokens


def test_overlong_prompt_rejected_before_any_state_change(engine):
    api, params = engine
    eng = ServeEngine(api, params, n_slots=2, max_seq=16)
    good = Request(0, np.arange(4, dtype=np.int32))
    bad = Request(1, np.arange(16, dtype=np.int32))  # == max_seq: no budget
    with pytest.raises(ValueError, match="prompt length 16 >= max_seq 16"):
        eng.submit_all([good, bad])
    # validation ran BEFORE anything was touched: clean engine, clean retry
    assert eng._pending_keys is None and eng._req_key_cache == {}
    assert eng.stats["prefills"] == 0 and not good.done
    eng.submit_all([good])
    assert good.done


def test_failed_submit_does_not_leak_fingerprint_state(engine, monkeypatch):
    api, params = engine
    eng = ServeEngine(api, params, n_slots=2, max_seq=64)
    reqs = [Request(i, np.arange(6, dtype=np.int32) + i) for i in range(4)]

    def boom(req, slot):
        raise RuntimeError("prefill OOM (simulated)")

    monkeypatch.setattr(eng, "_assign", boom)
    with pytest.raises(RuntimeError, match="prefill OOM"):
        eng.submit_all(reqs)
    # the in-flight key launch and this submission's cached keys are gone
    assert eng._pending_keys is None
    assert eng._req_key_cache == {}
    monkeypatch.undo()
    eng.submit_all(reqs)  # the retry starts clean and completes
    assert all(r.done for r in reqs)
    assert eng._req_key_cache == {}


def test_admission_front_door_rejects_duplicates(engine):
    from repro.hash import (AdmissionService, InProcessTransport,
                            VirtualClock, bloom_shard_backends)

    api, params = engine
    svc = AdmissionService(
        InProcessTransport(bloom_shard_backends(2, 1024)),
        clock=VirtualClock())
    eng = ServeEngine(api, params, n_slots=2, max_seq=64, admission=svc)
    rng = np.random.default_rng(3)
    uniq = [rng.integers(0, CFG.vocab_size, size=8).astype(np.int32)
            for _ in range(3)]
    reqs = [Request(i, uniq[i % 3].copy(), max_new_tokens=4)
            for i in range(6)]  # 3 unique prompts, each submitted twice
    eng.submit_all(reqs)
    assert all(r.done for r in reqs)
    admitted = [r for r in reqs if r.admitted]
    rejected = [r for r in reqs if r.admitted is False]
    assert len(admitted) == 3 and len(rejected) == 3
    assert all(len(r.out_tokens) == 4 for r in admitted)
    assert all(r.out_tokens == [] for r in rejected)  # never decoded
    assert eng.stats["admission_rejects"] == 3
    assert eng.stats["prefills"] == 3  # duplicates never cost a prefill
    assert eng.stats["degraded_ticks"] == 0


class _RaisingAdmission:
    """Stand-in admission service whose admit_batch raises `exc`."""

    degraded = False
    stats = {"l1_only_admits": 0}

    def __init__(self, exc):
        self.exc = exc

    def admit_batch(self, items):
        raise self.exc


def test_admission_bug_propagates_not_admitted(engine):
    """A failure that is not an admission outage (a kernel that fails to
    lower inside the filter) surfaces from submit_all: nothing is admitted
    and nothing is counted as an admission error."""
    api, params = engine
    eng = ServeEngine(api, params, n_slots=2, max_seq=64,
                      admission=_RaisingAdmission(
                          RuntimeError("Mosaic lowering failed (simulated)")))
    reqs = [Request(i, np.arange(6, dtype=np.int32) + i) for i in range(3)]
    with pytest.raises(RuntimeError, match="Mosaic"):
        eng.submit_all(reqs)
    assert all(r.admitted is None and not r.done for r in reqs)
    assert eng.stats["admission_errors"] == 0
    assert eng.stats["prefills"] == 0


def test_admission_outage_serves_everything(engine):
    """The service's own transport errors are an outage: the engine counts
    them and keeps answering requests."""
    from repro.hash.service import ShardUnavailable

    api, params = engine
    eng = ServeEngine(api, params, n_slots=2, max_seq=64,
                      admission=_RaisingAdmission(ShardUnavailable("down")))
    reqs = [Request(i, np.arange(6, dtype=np.int32) + i, max_new_tokens=3)
            for i in range(3)]
    eng.submit_all(reqs)
    assert all(r.admitted and r.done for r in reqs)
    assert eng.stats["admission_errors"] >= 1


@pytest.mark.slow  # model decode math, not engine/hash behaviour: full lane
def test_greedy_matches_manual_decode(engine):
    """Engine output == manual prefill+decode loop for a single request."""
    api, params = engine
    import jax.numpy as jnp

    prompt = np.arange(5, dtype=np.int32) + 3
    eng = ServeEngine(api, params, n_slots=1, max_seq=32)
    req = Request(0, prompt.copy(), max_new_tokens=4)
    eng.submit_all([req])

    logits, caches = api.prefill(params, {"tokens": jnp.asarray(prompt[None])},
                                 cache_len=32)
    toks = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(3):
        lg, caches = api.decode_step(params, caches,
                                     jnp.asarray([[toks[-1]]], jnp.int32),
                                     jnp.asarray(pos, jnp.int32))
        toks.append(int(jnp.argmax(lg[0])))
        pos += 1
    assert req.out_tokens == toks


def test_long_prompts_route_through_tree_path(engine):
    """Prompts at/past tree_prompt_words take the tree fingerprint (both in
    the batched precompute and the single-prompt fallback), and identical
    long prompts still hit the prefix cache."""
    api, params = engine
    eng = ServeEngine(api, params, n_slots=2, max_seq=64,
                      tree_prompt_words=8)
    rng = np.random.default_rng(7)
    long_p = rng.integers(0, CFG.vocab_size, size=12).astype(np.int32)
    short_p = rng.integers(0, CFG.vocab_size, size=4).astype(np.int32)
    # both key surfaces agree on the long prompt's fingerprint
    from repro.hash.tree import TreeSpec

    want = eng._tree_hasher().fingerprint(long_p.astype(np.uint32))
    assert eng._prompt_key(long_p) == want
    assert eng._tree_hasher().spec == TreeSpec(seed=0x1E53)
    eng._precompute_prompt_keys([Request(99, long_p.copy())])
    assert eng._req_key_cache.pop(99) == want
    assert eng._pending_keys is None  # no batched launch for a long-only wave
    # end-to-end: duplicate long prompts hit the prefix logits cache
    reqs = [Request(0, long_p.copy(), max_new_tokens=3),
            Request(1, short_p.copy(), max_new_tokens=3),
            Request(2, long_p.copy(), max_new_tokens=3)]
    eng.submit_all(reqs)
    assert all(r.done for r in reqs)
    assert eng.stats["prefix_hits"] == 1
    assert eng._req_key_cache == {}  # no leaked keys after the wave
