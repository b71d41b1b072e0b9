"""A whole run of the harness on the CPU at a tiny size, past its look
for a chip: sound runs come out correct, the fp8 control and the faults
a serving cell can have come out not correct, and ``run.py`` itself
refuses to run off the accelerator."""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import serve_bench_tiny as tiny
import catalog
import run

from repro.serve import scheduler as sched_mod

SEED = 2**35 + 21
SECONDS = 1.0


def _run(kind, tied=True, control=False):
    return run.run_cell(tiny.cell(kind, tied), tiny.mix(kind), SEED, SECONDS,
                        trace=False, devices=jax.devices(), control=control)


@pytest.mark.parametrize("kind,tied", [("chat_backlog", True),
                                       ("code_open", False)])
def test_a_sound_run_is_correct_and_its_control_is_not(kind, tied):
    result = _run(kind, tied)
    checks = result["checks"]
    assert list(result)[-1] == "checks"
    assert result["correct"], checks
    assert checks["logit_gap_max"]["value"] <= tiny.TINY_GAP_LIMIT
    names = {m["name"] for m in tiny.cell(kind, tied).end_to_end}
    assert set(result["metrics"]) == names
    assert result["attempted"] > 0 and result["failed"] == 0
    # the control in the program's place fails the run's own comparison
    control = _run(kind, tied, control=True)
    assert not control["correct"]
    assert not control["checks"]["logit_gap_max"]["ok"]
    assert control["checks"]["logit_gap_max"]["value"] > tiny.TINY_GAP_LIMIT
    assert control["checks"]["requests_failed"]["ok"]


def test_a_token_altered_where_it_is_produced_is_caught(monkeypatch):
    emit = sched_mod.ContinuousScheduler._emit

    def altered(self, slot, logits_row):
        # the sampled token moves one id over
        return emit(self, slot, np.roll(np.asarray(logits_row), 1))

    monkeypatch.setattr(sched_mod.ContinuousScheduler, "_emit", altered)
    result = _run("chat_backlog")
    assert not result["correct"]
    assert not result["checks"]["logit_gap_max"]["ok"]


def test_a_decode_step_that_returns_its_pools_unchanged_is_caught(
        monkeypatch):
    fns = sched_mod.ContinuousScheduler._paged_fns

    def unchanged(self):
        primary, degraded = fns(self)

        def keep(params, kp, vp, *rest):
            return primary(params, kp, vp, *rest)[0], (kp, vp)
        return keep, degraded

    monkeypatch.setattr(sched_mod.ContinuousScheduler, "_paged_fns",
                        unchanged)
    result = _run("chat_backlog")
    assert not result["correct"]
    assert not result["checks"]["logit_gap_max"]["ok"]


def _cli(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_off_the_accelerator():
    out = _cli(["benchmarks/serve/run.py", "--workload",
                "qwen3-1.7b.chat_backlog", "--seed", "5", "--seconds", "1",
                "--trace", "0"], cwd=catalog.ROOT)
    assert out.returncode != 0
    assert "accelerator" in out.stderr
    assert not out.stdout.strip()


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(catalog.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.SERVE, tmp_path / catalog.SUBDIR,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    out = _cli(["benchmarks/serve/run.py", "--workload",
                "qwen3-1.7b.chat_backlog", "--seed", "5", "--seconds", "1",
                "--trace", "1"], cwd=tmp_path, env_extra={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert not out.stdout.strip()
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.stdout.strip().splitlines()[-1] if out.stdout.strip()
                   else "")
