"""CPU rehearsal of ``chip_smoke.py``: its phases at the smoke-size model
with the Pallas kernels in interpret mode, and its refusal to run anywhere
but on a TPU."""
import dataclasses
import os
import sys

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402

REHEARSAL = chip_smoke.SmokeConfig(
    smoke=True, calib_batches=2, requests=4, prompt_len=(6, 12), max_new=4,
    max_batch=4, max_len=32, prefill_chunk=8, use_pallas="interpret")


def test_phases_serve_and_match_oracle_on_cpu():
    """generate(), the lookahead stream (greedy tokens equal to
    generate()'s) and the speculative run all complete every request;
    interpret-mode kernels agree with the oracle within the script's
    tolerance. The custom-call count is 0 off the chip: interpret mode
    lowers to plain HLO."""
    report = chip_smoke.run_serving(REHEARSAL, on_chip=False)
    assert report["custom_calls"] == 0
    assert report["kernel_err"] <= chip_smoke.KERNEL_RTOL * report[
        "logit_scale"]
    assert report["greedy_agree"] == 1.0
    assert report["tokens_per_s"] > 0


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs a TPU" in err


def test_engine_resolves_kernels_from_the_backend():
    """``use_pallas=None`` resolves once, at construction: kernels on a
    TPU only, so this CPU run gets the oracle."""
    cfg, state = chip_smoke.build_state(REHEARSAL)
    sc = dataclasses.replace(REHEARSAL, use_pallas=None)
    engine = chip_smoke.make_engine(sc, cfg, state)
    assert engine.use_pallas is (jax.default_backend() == "tpu")
