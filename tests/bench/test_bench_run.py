"""A whole benchmark run on the CPU at a tiny size, minus the look for a
chip: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false.  And ``bench/run.py`` refuses
to run without a TPU or without the serving program."""
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness
from conftest import ROOT, tiny


@pytest.fixture(scope="module")
def cell():
    c = tiny(harness.load_cell("qwen2-1.5b-mux2.chat"))
    assert c["per_layer"]
    # every finished row group is compared, not a sample of four: which
    # groups finish in the window depends on the CPU's speed, and a
    # decode step that drops its KV moves some groups' tokens only
    c["config"]["check"].update(logit_gap_limit=0.004, max_groups=10**6,
                                tokens=10**6)
    # outputs long enough that tokens decoded early are attended by later
    # ones (a decode step that drops its KV must show)
    c["traffic"]["output_len"].update(median=20, min=12, max=24)
    return c


def _run(cell, control=False):
    return harness.run_cell(cell, 3_000_000_011, 2.0, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            control=control)


def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["checks"]["window_compiles"]["value"] == 0
    assert out["attempted"] == 6
    # the cell's end-to-end metrics, as BENCHMARK.json declares them
    assert set(out["metrics"]) == set(cell["end_to_end"]) == {
        "setup_s", "itl_p95_s", "out_tok_s"}
    assert list(out)[-1] == "checks"


def test_control_run_is_not_correct(cell):
    out = _run(cell, control=True)
    assert out["correct"] is False
    assert out["readings"]["program_gap_max"] <= 0.004
    assert out["checks"]["logit_gap_max"]["value"] > 0.004


def _break(monkeypatch, fault):
    from repro.serve.runtime import ServeRuntime
    orig = ServeRuntime._decode_impl

    def broken(self, params, cache, *args):
        toks, new = orig(self, params, cache, *args)
        if fault == "token":          # every decoded token altered
            return (toks + 1) % self.sc.cfg.vocab_size, new
        return toks, cache            # the step returns its state unchanged
    monkeypatch.setattr(ServeRuntime, "_decode_impl", broken)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_step_is_not_correct(cell, monkeypatch, fault):
    _break(monkeypatch, fault)
    out = _run(cell)
    assert not out["correct"]
    assert out["checks"]["logit_gap_max"]["value"] > 0.004


def test_run_refuses_a_cpu_backend():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "qwen2-1.5b-mux2.chat", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-1.5b-mux2.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
