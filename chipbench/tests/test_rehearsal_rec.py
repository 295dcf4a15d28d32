"""The new cell as the driver runs it — ``run.py`` in a process of its
own — at its rehearsal size on the CPU: a sound run exits 3 (every phase
agreed with the model; a rehearsal never exits 0), each control exits 1
with ``correct`` false; and the driver's question to the program: a
program whose device path does not take the configuration's widths ends
the run, nonzero, before a file is built."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import cluster as cl
from chipbench.drivers import refresh_rec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "rec1k_32x20k.refresh"


def run_py(*extra, seed=2**31 + 29):
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "2", "--rehearse", *extra],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    marked = [line for line in out.stdout.splitlines()
              if "REHEARSAL ONLY" in line]
    result = json.loads(marked[-1].split("not a chip run: ", 1)[1]
                        ) if marked else None
    return out, result


def test_sound_rehearsal_exits_3_and_says_which_path_ran():
    out, result = run_py("--trace", "1")
    assert out.returncode == 3, out.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["window_compilations"] == 0
    assert result["metrics"]["index_path_pct"] == {"value": 100.0,
                                                   "unit": "%"}
    assert result["compared"]["host_fallbacks"]["value"] == 0
    assert "one_shard_index" in out.stdout  # the set-up's compile log


@pytest.mark.parametrize("control", ["bits32", "fold32"])
def test_control_exits_1_with_correct_false(control):
    out, result = run_py("--control", control)
    assert out.returncode == 1
    assert result["correct"] is False
    assert result["compared"]["mismatched_answers"]["value"] > 0
    assert result["compared"]["failed_rpcs"]["value"] == 0


def config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "rec1k_32x20k.json")) as f:
        return json.load(f)


def test_prepare_asks_the_program_and_builds_nothing_where_it_says_no(
        tmp_path, monkeypatch):
    from rocksplicator_tpu.tpu import compaction_service as cs

    assert refresh_rec.device_takes(config()) == ""
    counters = dict(config(), options=dict(config()["options"],
                                           merge_operator="uint64add"))
    assert "up to 8 B" in refresh_rec.device_takes(counters)
    monkeypatch.setattr(cs, "device_value_bytes_max", lambda op: 8)
    assert "up to 8 B" in refresh_rec.device_takes(config())
    driver = refresh_rec.make(None, str(tmp_path), config(), {}, 1, None)
    with pytest.raises(SystemExit) as e:
        driver.prepare()
    assert e.value.code not in (0, None) and driver.child is None
    assert os.listdir(tmp_path) == []
    # a program from before the function (the parent commit) cannot say
    monkeypatch.delattr(cs, "device_value_bytes_max")
    assert "cannot say" in refresh_rec.device_takes(config())
    assert cl.options_generator(config()["options"])(
        "seg").merge_operator is None
