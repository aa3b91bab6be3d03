"""``chip_smoke.py`` at toy size on the CPU.

The phases' control flow and every reference check (replay, oracle engine,
exact counts, monitor replay) run here, so a change that breaks the smoke
fails without chip time.  The two TPU-only proofs -- kernel dispatch
counters reading ``pallas_tpu`` and the Mosaic kernel in the flush program
-- cannot hold on the CPU and are stubbed in these tests only.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


@pytest.fixture
def tpu_proofs_stubbed(monkeypatch):
    monkeypatch.setattr(chip_smoke, "_kernel_impls", lambda: {
        "fused_query": {"pallas_tpu"}, "fused_pairs": {"pallas_tpu"}})
    monkeypatch.setattr(chip_smoke, "_flush_runs_kernel", lambda *a: True)


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_main_refuses_forced_kernel_impl(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "jnp_ref")
    assert chip_smoke.main([]) == 2
    assert capsys.readouterr().out == ""


def test_service_phase_toy_size(tpu_proofs_stubbed):
    out = chip_smoke.phase_service(0, tenants=8, sample_tenants=4,
                                   self_queries=8, join_queries=2,
                                   replay_tenants=4, exact_tenants=4)
    assert out["records"] == (8 + 2 * 4) * chip_smoke.ROWS * chip_smoke.FLUSHES
    assert len(out["poll_s"]) == chip_smoke.FLUSHES
    assert out["exact_checked_tenants"] == 4
    assert out["worst_rel_diff_vs_oracle"] <= 1e-6


def test_train_phase_toy_size():
    out = chip_smoke.phase_train(0, reduced=True, batch=2, seq=64, steps=3)
    assert out["steps"] == 3 and out["cuts"] == []
    assert len(out["loss"]) == 3
