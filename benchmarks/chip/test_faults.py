"""A whole run of the harness on the CPU at a tiny size, past the look
for a chip: sound, it comes out correct; with the served path broken
underneath, ``correct`` comes out false. Each fault is one that a
served cell can have:

* ``token``: a served token altered where it is produced;
* ``state``: a decode step that returns the KV pool unchanged;
* ``half_batch``: half of the decode batch left out, its rows given
  the other half's logits.

The cells run on one chip, so there is no exchange between chips to
leave out. The control, the reference computed in int8 in the
program's place, has to come out not correct where the program comes
out correct, on every seed. At the tiny size int8 is hardly worse than
bfloat16, so that case runs four times wider and three times deeper
(``SMALL``), where the two separate on every seed tried.

The runs go in one child process with JAX's asynchronous CPU dispatch
off. With it on, the engine's overlapped decode hands host arrays
(lengths, block table) to a dispatch that the CPU backend may read
without a copy, and mutates them right after: about one tiny run in
five then serves wrong tokens on the CPU. The chip copies its inputs
before the call returns. The flag is read when the CPU client is made,
which in a test worker has happened long before, hence the child.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent

CFG = {"name": "tiny-yi", "reference": "dense_decoder",
       "program": {"config": "yi_6b",
                   "overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                                 "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                                 "vocab_size": 256}},
       "dtype": "bfloat16", "hidden_size": 64, "intermediate_size": 128,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_hidden_layers": 2, "vocab_size": 256, "hidden_act": "silu",
       "rms_norm_eps": 1e-5, "rope_theta": 5e6,
       "max_position_embeddings": 256, "tie_word_embeddings": False}
MIX = {"loop": "closed", "slots": 4, "requests": 4096,
       "prompt": {"dist": "loguniform", "min": 8, "max": 16},
       "output": {"dist": "loguniform", "min": 8, "max": 24},
       "greedy_share": 0.5, "temperature": 0.8, "top_p": 0.95,
       "block_size": 4, "num_blocks": 65, "max_len": 48,
       "max_prefill_batch": 1, "kv_dtype": "bf16"}
# At this size the logits spread about 0.8 (8 x 0.1): a sound bf16 run
# reads gaps of a few hundredths, a wrong token one of tenths to units.
LIMITS = {"logit_gap": 0.15, "min_tokens": 48, "max_requests": 6}

# Four times wider and three times deeper, with 5 s windows and at
# least 160 tokens compared, so that a loaded CPU still finishes enough
# requests. On the CPU over seeds 1-8 the program read 0.045-0.126 and
# the int8 control 0.478-0.754 (with 3 s windows, 0.045-0.198 and
# 0.478-0.823; with 64 tokens compared the control read 0.266 on one
# seed; at 2 layers the two overlapped within 1.7 times). The limit lies
# between.
SMALL = dict(CFG, hidden_size=256, intermediate_size=512,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=1024,
             num_hidden_layers=6,
             program={"config": "yi_6b",
                      "overrides": {"n_layers": 6, "d_model": 256,
                                    "n_heads": 4, "n_kv_heads": 2,
                                    "head_dim": 64, "d_ff": 512,
                                    "vocab_size": 1024}})
SMALL_MIX = dict(MIX, prompt={"dist": "loguniform", "min": 16, "max": 64},
                 output={"dist": "loguniform", "min": 16, "max": 48},
                 block_size=8, max_len=112)
SMALL_LIMITS = {"logit_gap": 0.3, "min_tokens": 160, "max_requests": 16}
CONTROL_SEEDS = tuple(range(1, 9))


def _run(seed, cfg=CFG, mix=MIX, limits=LIMITS, seconds=1.0,
         control=False):
    from chip import harness

    return harness.run({"name": "tiny", "chips": 1}, cfg, mix, limits,
                       seed=seed, seconds=seconds, trace=False,
                       t_start=time.monotonic(), peak=None, control=control)


def _break(fault):
    """Break the served path underneath the harness, in this process."""
    import jax.numpy as jnp

    from repro.launch.engine import scheduler
    from repro.models.model import Model

    if fault == "token":
        orig = scheduler.register_sample

        def altered(req, tok, eos_id, on_finish):
            if req.sampling.greedy and req._n_sampled == 2:
                tok = (tok + 1) % 256
            return orig(req, tok, eos_id, on_finish)

        scheduler.register_sample = altered
        return
    orig_step = Model.decode_step_paged

    def step(self, params, pools, table, lengths, tokens, ctx, **kw):
        logits, new = orig_step(self, params, pools, table, lengths, tokens,
                                ctx, **kw)
        if fault == "state":
            return logits, pools
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), new

    Model.decode_step_paged = step


FAULTS = ("sound", "token", "state", "half_batch")


def _child():
    """Run every case, each after the one before has been undone, and
    print one JSON line per case."""
    import jax

    jax.config.update("jax_cpu_enable_async_dispatch", False)

    from repro.launch.engine import scheduler
    from repro.models.model import Model

    saved = (scheduler.register_sample, Model.decode_step_paged)
    for fault in FAULTS:
        if fault != "sound":
            _break(fault)
        res = _run(seed=2 ** 31 + 11)
        scheduler.register_sample, Model.decode_step_paged = saved
        print(json.dumps({"case": fault, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    for seed in CONTROL_SEEDS:
        res = _run(seed=seed, cfg=SMALL, mix=SMALL_MIX, limits=SMALL_LIMITS,
                   seconds=5.0, control=True)
        print(json.dumps({"case": f"control.{seed}",
                          "correct": res["correct"], "checks": res["checks"],
                          "control_correct": res["control_correct"],
                          "control_checks": res["control_checks"]}),
              flush=True)


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE.parent), str(HERE.parents[1] / "src")]))
    out = subprocess.run([sys.executable, str(Path(__file__))], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return {r["case"]: r for r in map(json.loads, out.stdout.splitlines())}


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_makes_the_run_incorrect(cases, fault):
    res = cases[fault]
    assert res["checks"]["tokens_compared"]["value"] >= LIMITS["min_tokens"]
    assert res["checks"]["short_outputs"]["value"] == 0
    assert res["correct"] is (fault == "sound"), res["checks"]


def test_control_fails_the_limit_the_program_passes(cases):
    """On every seed the program comes out correct and the int8 control,
    judged in its place by the same comparison, comes out incorrect."""
    for seed in CONTROL_SEEDS:
        res = cases[f"control.{seed}"]
        assert res["correct"], (seed, res["checks"])
        assert res["control_correct"] is False, (seed, res["control_checks"])
        assert res["control_checks"]["tokens_compared"] == \
            res["checks"]["tokens_compared"]


if __name__ == "__main__":
    sys.path[0] = str(HERE.parent)
    _child()
