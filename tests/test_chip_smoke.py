"""chip_smoke.py's phases on the CPU at smoke size, with the Pallas
kernels in interpret mode: the same code the chip runs at olmo-1b's
published widths."""

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.launch.engine import EngineConfig  # noqa: E402
from repro.models.transformer import RunCtx  # noqa: E402

INTERPRET = RunCtx(kernel_mode="interpret")
ECFG = EngineConfig(backend="paged", num_slots=4, block_size=8,
                    num_blocks=33, max_len=64, overlap=True)


@pytest.fixture(scope="module")
def smoke_model():
    return chip_smoke.build(get_config("olmo_1b").smoke(), 0)


def test_kernels_match_references(smoke_model):
    model, params = smoke_model
    gap = chip_smoke.check_kernels(model, params, "interpret",
                                   prompt_lens=(29, 12), steps=3)
    assert 0.0 <= gap["max_abs_diff"] <= gap["tol"]
    assert gap["tol"] == chip_smoke.LOGIT_RTOL * gap["ref_max_abs"] > 0


def test_kernel_check_fails_on_a_wrong_path(smoke_model, monkeypatch):
    """The comparison is live: a kernel path that scales its logits
    trips the tolerance."""
    model, params = smoke_model
    real = chip_smoke.trace_logits

    def skewed(model, params, ctx, *a, **k):
        out = real(model, params, ctx, *a, **k)
        return out * 1.1 if ctx.kernel_mode == "interpret" else out

    monkeypatch.setattr(chip_smoke, "trace_logits", skewed)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_kernels(model, params, "interpret",
                                 prompt_lens=(9,), steps=1)


def test_serving_drains_and_overlap_matches(smoke_model):
    model, params = smoke_model
    reqs = chip_smoke.request_mix(model.cfg.vocab_size, 0, (10, 40), 6,
                                  new_tokens=(4, 12))
    assert sum(s.greedy for _, s in reqs) == 3
    res = chip_smoke.serve_overlap_pair(model, params, ECFG, reqs,
                                        ctx=INTERPRET)
    assert res["identical"] == res["requests"] == 6
    assert res["engine"].stats()["blocks_used"] == 0
    assert res["pool_bytes"] > 0


def test_overlap_check_catches_a_sampled_divergence(smoke_model,
                                                   monkeypatch):
    """A sampled request that differs between overlap on and off fails
    the pair, as a greedy one would."""
    model, params = smoke_model
    reqs = chip_smoke.request_mix(model.cfg.vocab_size, 3, (10,), 2,
                                  new_tokens=(4, 4))
    real = chip_smoke.serve

    def skewed(*a, **k):
        eng, handles = real(*a, **k)
        if a[2].overlap is False:
            handles[1].token_ids[-1] += 1      # request 1 is sampled
        return eng, handles

    monkeypatch.setattr(chip_smoke, "serve", skewed)
    with pytest.raises(chip_smoke.SmokeFailure, match=r"\[1\]"):
        chip_smoke.serve_overlap_pair(model, params, ECFG, reqs,
                                      ctx=INTERPRET)


def test_short_passes_drain(smoke_model):
    model, params = smoke_model
    reqs = chip_smoke.request_mix(model.cfg.vocab_size, 1, (24,), 4,
                                  new_tokens=(4, 8), repeat=6)
    res = chip_smoke.short_passes(model, params, ECFG, reqs, ctx=INTERPRET)
    assert res["proposed"] > 0


def test_drain_check_catches_short_outputs(smoke_model):
    model, params = smoke_model
    reqs = chip_smoke.request_mix(model.cfg.vocab_size, 2, (10,), 2,
                                  new_tokens=(4, 4))
    eng, handles = chip_smoke.serve(
        model, params, dataclasses.replace(ECFG, overlap=False), reqs,
        ctx=INTERPRET)
    handles[0].token_ids.pop()
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_drained("t", [eng], handles)


def test_main_refuses_without_a_tpu(monkeypatch, capsys):
    """No accelerator: a non-zero exit before any model is built or any
    cache is configured, and no result line."""
    work = []
    monkeypatch.setattr(chip_smoke, "build", lambda *a: work.append("b"))
    monkeypatch.setattr(chip_smoke.compile_cache, "enable",
                        lambda: work.append("c"))
    assert chip_smoke.main([]) != 0
    assert work == []
    assert '"ok"' not in capsys.readouterr().out


def test_engine_defaults_to_platform_kernels(smoke_model):
    """With no ctx the Engine lets the platform pick: the Pallas kernels
    on a TPU, the references here. The compile cache defaults to the
    checkout's own ``.jax_cache`` (listed in .gitignore)."""
    from repro.kernels import ops
    from repro.launch import compile_cache
    from repro.launch.engine import Engine

    model, params = smoke_model
    assert Engine(model, params, ECFG).backend.ctx.kernel_mode == "auto"
    assert ops._use_pallas("auto") == (False, False)
    assert compile_cache.CHECKOUT_CACHE == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_multi_chip_body_on_four_cpu_devices():
    """The ``--chips 4`` body on four virtual CPU devices: replicas each
    on their own device and a head-sharded tp=4 engine, both
    token-identical to one device. Four kv heads so they divide tp."""
    code = textwrap.dedent("""
        import dataclasses
        import chip_smoke
        from repro.configs import get_config
        from repro.launch.engine import EngineConfig
        from repro.models.transformer import RunCtx
        cfg = dataclasses.replace(get_config("olmo_1b").smoke(),
                                  n_heads=4, n_kv_heads=4)
        model, params = chip_smoke.build(cfg, 0)
        ecfg = EngineConfig(backend="paged", num_slots=4, block_size=8,
                            num_blocks=65, max_len=128, overlap=True)
        reqs = chip_smoke.request_mix(cfg.vocab_size, 0, (20, 60), 8,
                                      new_tokens=(4, 12))
        print(chip_smoke.multi_chip(model, params, ecfg, reqs,
                                    ctx=RunCtx(kernel_mode="interpret")))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "placed_on_own_chip=[True, True, True, True]" in proc.stdout
    assert "head_sharded_decode=True" in proc.stdout
    assert "{'dp_identical': 8, 'tp_identical': 8}" in proc.stdout
