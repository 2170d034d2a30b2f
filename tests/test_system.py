"""End-to-end system test: the full public API path in one scenario --
hash-powered pipeline -> model -> sharded-ish train steps -> verified
checkpoint -> serving engine. (Replaces the scaffold placeholder.)"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.pipeline import HashPipeline, PipelineConfig
from repro.data.synthetic import corpus
from repro.models import build
from repro.serve import Request, ServeEngine
from repro.train import Trainer, TrainerConfig


# full-lane suite: excluded from the CI fast lane (pytest -m "not slow")
pytestmark = pytest.mark.slow


def test_full_system_path(tmp_path):
    cfg = get_config("mistral_nemo_12b", smoke=True)
    api = build(cfg)

    # 1. data: dedup + split + pack through the paper's hash families
    pipe = HashPipeline(PipelineConfig(seq_len=16, batch_size=4, eval_pct=2,
                                       dedup=True))
    batches = []
    for b in pipe.pack(corpus(seed=11, n_docs=3000, vocab=cfg.vocab_size,
                              dup_rate=0.1)):
        batches.append({k: jnp.asarray(v) for k, v in b.items()})
        if len(batches) >= 64:
            break
    # routing stats need a larger sample than the 64 packed batches consume
    for doc in corpus(seed=99, n_docs=400, vocab=cfg.vocab_size, dup_rate=0.15):
        pipe.admit(doc)
    assert pipe.stats["dup"] > 0
    assert pipe.stats["eval"] > 0

    # 2. train with periodic verified checkpoints
    tc = TrainerConfig(total_steps=12, checkpoint_every=6, log_every=4,
                       checkpoint_dir=str(tmp_path), peak_lr=2e-3,
                       warmup_steps=3)
    tr = Trainer(api, tc)
    state = tr.train(iter(batches * 4))
    assert int(state.step) == 12
    assert tr.ckpt.latest_valid() == 12
    assert all(np.isfinite(m["loss"]) for m in tr.metrics_log)

    # 3. serve from the trained params
    eng = ServeEngine(api, state.params, n_slots=2, max_seq=48)
    reqs = [Request(i, np.arange(6, dtype=np.int32) + i, max_new_tokens=4)
            for i in range(3)]
    eng.submit_all(reqs)
    assert all(r.done and len(r.out_tokens) == 4 for r in reqs)
