"""CPU rehearsal of chip_smoke.py: its phase functions on the reduced
example-100m config, 2 parties and 1 round, with the kernels interpreted.
Only ``main()`` insists on a TPU. Each phase drains the queue with its own
consumer groups, so each runs once per training run (as in ``main()``)."""
import pytest

import chip_smoke
from repro import configs


@pytest.fixture(scope="module")
def run():
    cfg = configs.get_config("example-100m").reduced()
    result, round_end = chip_smoke.train(cfg, 2, 1, n_sequences=32)
    return result, round_end


@pytest.fixture(scope="module")
def phase_a(run):
    return chip_smoke.check_rounds(run[0])


def test_train_publishes_one_fused_model_per_round(run):
    result, round_end = run
    assert len(result.records) == len(round_end) == 1
    assert result.runtime.agg.alg.name == "fedprox"


def test_phase_a_fold_matches_jnp_reference(phase_a):
    a = phase_a
    assert a["ok"], a
    assert a["max_err"] <= a["tol"]
    assert len(a["folds"]) == 1


def test_phase_b_resume_equals_uninterrupted_fold(run, phase_a):
    b = chip_smoke.check_resume(run[0], phase_a["folds"][0])
    assert b["ok"], b
    assert b["drained"] == (1, 1) and b["max_err"] == 0.0


def test_phase_c_batch_and_int8_drains_match_oracles(run):
    c = chip_smoke.check_drains(run[0], k=2)
    assert c["ok"], c


def test_phase_d_kernels_are_interpreted_on_cpu(run):
    assert chip_smoke.kernel_programs(run[0]) == {
        "pair_fuse": False, "fused_agg": False, "quant_agg": False}


def test_main_refuses_cpu_and_prints_no_result(capsys):
    assert chip_smoke.main() == 1
    assert '"ok"' not in capsys.readouterr().out
