"""The check's control: the reference computed at a lower precision, put
in the program's place, is not correct. At smoke size on the CPU, where
XLA computes every float32 matmul in full, only the bfloat16 control is
lower; the chip readings of every control, which set the cells' limits,
are in PERF.md."""
import types

import jax
import jax.numpy as jnp
import numpy as np

import arch
import reference
import run
import smoke
from repro.serving import Request

SEED = 4242
PROMPT, NEW = 24, 32


def _spec():
    sp = smoke.spec()
    # a vocabulary large enough for near ties among the top logits
    sp["conf"]["model"].update(vocab_size=8192, d_model=128, d_ff=256)
    return sp


def _control_decode(ref, prompt, new, kind):
    """Greedy decoding by the reference's forward at the control's
    precision: the tokens a lower-precision program would serve."""
    dtype, precision = reference.CONTROLS[kind]
    fwd = jax.jit(lambda w, r, t: arch.module(ref.conf).forward(
        w, r, t, ref.conf, dtype=dtype, precision=precision))
    seq = list(prompt)
    for _ in range(new):
        logits = fwd(ref.w, ref.ranks, ref._pad(np.asarray(seq, np.int32)))
        seq.append(int(jnp.argmax(logits[len(seq) - 1])))
    return seq[len(prompt):]


def _as_run(prompts, served):
    """What ``run.check`` reads of a served window: greedy window
    requests, each with its prompt and served tokens."""
    records = [types.SimpleNamespace(
        arrival=types.SimpleNamespace(phase="window", greedy=True, index=i,
                                      prompt=p, max_new=len(s)),
        tokens=list(s), complete=True)
        for i, (p, s) in enumerate(zip(prompts, served))]
    return types.SimpleNamespace(records=records)


def test_the_bfloat16_control_reads_far_above_the_program():
    sp = _spec()
    sp["mix"]["greedy_share"] = 1.0       # greedy requests only
    engine, row, row_params = run.build(sp, SEED, use_pallas=False,
                                        log=lambda s: None)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 8192, PROMPT).astype(np.int32)
               for _ in range(8)]
    results = engine.generate(
        [Request(prompt=p, max_new_tokens=NEW, budget=sp["mix"]["budget"])
         for p in prompts], mode="continuous")
    program = [[int(t) for t in rs.tokens[PROMPT:]] for rs in results]
    ref = reference.Reference(sp["conf"], SEED, sp["mix"]["budget"], 64)
    control = [_control_decode(ref, p, NEW, "bfloat16") for p in prompts]
    assert control != program
    # the check passes the program and fails the control in its place
    numbers, ok = run.check(sp, SEED, _as_run(prompts, program), row_params,
                            log=lambda s: None)
    assert ok and numbers["greedy_gap"]["value"] == 0.0
    numbers, ok = run.check(sp, SEED, _as_run(prompts, control), row_params,
                            log=lambda s: None)
    assert not ok
    assert numbers["greedy_gap"]["value"] > numbers["greedy_gap"]["limit"]
    # the control's own readings at the same positions (what the chip's
    # probe reads): some first choices move, by a clear gap
    gaps = np.concatenate([ref.control_gaps(p, np.asarray(s, np.int32),
                                            "bfloat16")
                           for p, s in zip(prompts, program)])
    assert (gaps > 0).sum() >= 2 and gaps.max() > 1e-3
