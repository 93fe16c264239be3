"""`correct` has to come out false when the timed path is broken underneath.

Run by hand (`JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q`); not
part of the repo's tier-1 `tests/`. The two run tests drive the whole
harness on the CPU at a tiny size (the rehearsal cell, a 256-lane sidecar),
with the look for a chip skipped by `JAX_PLATFORMS=cpu`; each takes a minute
or two once the CPU program is in the compile cache, several the first time.

  skip_half  the control: the sidecar reports every second lane valid
             unchecked (an answer altered where it is produced)
  alter_tx   node 0 flips a bit in every 97th transaction before sealing
             (what is committed is not what was sent)
and, on hand-made sources, the consensus faults a run cannot plant cheaply:
a fork, a silent node, a probe request that never came back.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REHEARSAL = os.path.join(ROOT, "chipbench", "tests", "rehearsal.json")


def _run(fault, seed, tmp):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [
        sys.executable, "-m", "chipbench.run", "--bench-file", REHEARSAL,
        "--workload", "tiny.flood", "--seed", str(seed), "--seconds", "6",
        "--trace", "0", "--out", str(tmp),
    ]
    if fault:
        cmd += ["--fault", fault]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "fault,number",
    [(None, None), ("skip_half", "probe_lanes_wrong"), ("alter_tx", "tx_not_as_sent")],
)
def test_run_with_fault(fault, number, tmp_path):
    res = _run(fault, 4_000_000_007, tmp_path / "run")
    over = {k: v for k, (v, limit) in res["compared"].items() if v > limit}
    if fault is None:
        assert res["correct"] is True and not over, over
    else:
        assert res["correct"] is False
        assert number in over, over


def _sources():
    """Two nodes, two rounds in the window, everything else in order."""
    node = lambda: {  # noqa: E731
        "blocks": [(10.5, 1, "A"), (11.5, 2, "B")],
        "payload_commits": [], "created": [], "own_payloads": {}, "samples": {},
        "verify": [], "verify_failed": 0, "warnings": 0, "errors": [],
        "snapshots": [
            (9.0, {"counters": {}, "histograms": {}, "info": {"backend": {"tpu_sigs": 0}}}),
            (12.0, {"counters": {}, "histograms": {}, "info": {"backend": {"tpu_sigs": 9}}}),
        ],
    }
    return {
        "seed": 1,
        "config": {"tx_size": 512, "nodes": 2},
        "traffic": {"rate": 40.0, "tick_s": 0.05},
        "window": {"start": 9.0, "t0": 10.0, "t1": 12.0, "seconds": 2.0, "end": 13.0},
        "records": [],
        "committee_pubs": [b"a" * 32, b"b" * 32],
        "nodes": [node(), node()],
        "sidecar": node(),
        "probe": {"corpus": [], "answers": []},
    }


def _judge(src, tmp_path):
    from chipbench import judge

    compared = judge.judge(src, str(tmp_path))
    return judge.correct(compared), compared


def test_sound_sources_are_correct(tmp_path):
    ok, compared = _judge(_sources(), tmp_path)
    assert ok, compared


def test_fork_is_not_correct(tmp_path):
    src = _sources()
    src["nodes"][1]["blocks"][1] = (11.5, 2, "C")
    ok, compared = _judge(src, tmp_path)
    assert not ok and compared["chain_forks"][0] == 1


def test_silent_node_is_not_correct(tmp_path):
    src = _sources()
    src["nodes"][1]["blocks"] = [(5.0, 1, "A")]
    ok, compared = _judge(src, tmp_path)
    assert not ok and compared["nodes_silent"][0] == 1


def test_unanswered_probe_is_not_correct(tmp_path):
    from chipbench import reference as ref

    src = _sources()
    src["probe"] = {"corpus": ref.probe_corpus(3, 1, 16, 5), "answers": [None]}
    ok, compared = _judge(src, tmp_path)
    assert not ok and compared["probe_unanswered"][0] == 1


def test_yes_to_everything_is_not_correct(tmp_path):
    from chipbench import reference as ref

    src = _sources()
    corpus = ref.probe_corpus(3, 1, 16, 5)
    src["probe"] = {"corpus": corpus, "answers": [[True] * 16]}
    ok, compared = _judge(src, tmp_path)
    assert not ok and compared["probe_lanes_wrong"][0] == 3
