"""The checks tell the reference at the configuration's precision from
the control one precision lower, and from each fault a cell can have,
with the reference put in the program's place (``tools/controls.py`` at
the rehearsal sizes; on the chip it runs at the cells' own sizes)."""
import pytest


def _rows(jax, workload, seeds):
    import run as bench_run
    from tools import controls
    bench, _, cfg, mix = bench_run.load_cell(workload)
    cfg, mix = bench_run.rehearsal(cfg, mix)
    out = {}
    for seed in seeds:
        rows = (controls.train_rows(jax, cfg, mix, seed,
                                    controls.TRAIN_VARIANTS)
                if cfg["kind"] == "train" else
                controls.serve_rows(jax, cfg, mix, seed, 5.0,
                                    controls.SERVE_VARIANTS))
        for var, outcome in rows:
            out[(seed, var)] = controls.run_checks(cfg, outcome)[1]
    return out


# bf16_arith (bfloat16 transition arithmetic over int32 counts) passes:
# no number of the training check can see it (PERF.md)
@pytest.mark.parametrize("workload,sound,failing", [
    ("train-pubmed-k1000", ("f32", "bf16_arith"),
     ("bf16", "unchanged", "half", "token")),
    ("serve-pubmed-k1000-steady", ("f64", "f32"), ("bf16", "answer")),
])
def test_controls_and_faults_fail_sound_passes(jax, workload, sound,
                                               failing):
    got = _rows(jax, workload, seeds=(21, 22))
    for (seed, var), correct in got.items():
        assert correct == (var in sound), (workload, seed, var)
    assert {v for _, v in got} == set(sound) | set(failing)
