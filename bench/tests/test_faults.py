"""A whole run at the rehearsal sizes, past the harness's look for a chip,
with the timed path broken underneath: ``correct`` must come out false
for each fault the cell can have, and true when nothing is broken; and
a real run, which refuses to start without a TPU."""
import argparse

import numpy as np
import pytest


def _execute(jax, workload, seed=7):
    import run as bench_run
    args = argparse.Namespace(workload=workload, seed=seed, seconds=2.0,
                              trace=0, rehearse=True)
    return bench_run.execute(args, jax)[0]


@pytest.mark.parametrize("workload", ["train-pubmed-k1000",
                                      "serve-pubmed-k1000-steady"])
def test_sound_run_is_correct(jax, workload):
    assert _execute(jax, workload)["correct"]


def test_state_returned_unchanged(jax, monkeypatch):
    from repro.core.engine import ModelParallelLDA
    monkeypatch.setattr(ModelParallelLDA, "step", lambda self: None)
    res = _execute(jax, "train-pubmed-k1000")
    assert not res["correct"]
    assert res["checks"]["stay_z"]["value"] > res["checks"]["stay_z"][
        "limit"]


def test_half_the_tokens_left_out(jax, monkeypatch):
    import jax.numpy as jnp

    from repro.core.engine import ModelParallelLDA

    def half(self):
        m = self.layout.mask
        return m & (jnp.arange(m.shape[-1]) % 2 == 0)
    monkeypatch.setattr(ModelParallelLDA, "mask", property(half))
    res = _execute(jax, "train-pubmed-k1000")
    assert not res["correct"]
    assert res["checks"]["stay_z"]["value"] > res["checks"]["stay_z"][
        "limit"]


def test_token_altered_where_it_is_produced(jax, monkeypatch):
    from repro.core.engine import ModelParallelLDA
    from repro.core.engine import state as engine_state
    step = ModelParallelLDA.step

    def altered(self):
        step(self)
        s = self.state
        pos = np.argwhere(np.asarray(self.mask))[0]
        z = s.z.at[tuple(pos)].set((s.z[tuple(pos)] + 1) % self.num_topics)
        self.state = engine_state.MPState(s.cdk, s.ckt, s.block_id,
                                          s.ck_synced, s.ck_local, z)
    monkeypatch.setattr(ModelParallelLDA, "step", altered)
    res = _execute(jax, "train-pubmed-k1000")
    assert not res["correct"]
    assert res["checks"]["count_mismatch"]["value"] > 0


def test_answer_altered_where_it_is_produced(jax, monkeypatch):
    from repro.serve.topic_infer import TopicInferenceServer
    infer = TopicInferenceServer.infer_with_draws
    calls = []

    def altered(self, docs, z0_rows, u_rows):
        theta = infer(self, docs, z0_rows, u_rows)
        if not calls:
            k = theta.shape[1]
            a = self.snapshot.alpha.astype(np.float64)
            c = np.rint(theta[0] * (len(docs[0]) + a.sum()) - a)
            top = int(np.argmax(c))
            c[top] -= 1
            c[(top + 1) % k] += 1
            theta = theta.copy()
            theta[0] = (c + a) / (c.sum() + a.sum())
        calls.append(1)
        return theta
    monkeypatch.setattr(TopicInferenceServer, "infer_with_draws", altered)
    res = _execute(jax, "serve-pubmed-k1000-steady")
    assert not res["correct"]
    assert res["checks"]["unexplained_answers"]["value"] >= 1


def test_refuses_to_run_without_a_tpu():
    import os
    import subprocess
    import sys
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(bench, "run.py"), "--workload",
         "serve-pubmed-k1000-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "expected a tpu device" in out.stderr
