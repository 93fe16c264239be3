"""Boot one committee on this host: one sidecar that holds the chip, n nodes,
one load generator. Copied from benchmark/local.py `LocalBench` and
chip_smoke.py `phase_served`, with three things changed: the sidecar and the
nodes start at the same moment (the nodes' synthetic pools are made while the
sidecar warms up), every process logs at INFO (the program's argparse
default; `CommandMaker` adds `-vv` to it and lands on DEBUG), and the
committee's keys come from the seed, and a configuration's `faults` f are
its last f members, which never boot (upstream's `local.py`: the committee
file names all n, `range(n - f)` start). This module never imports JAX.
"""

from __future__ import annotations

import base64
import json
import os
import signal
import subprocess
import sys
import time

from . import arith
from . import reference as ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE_PORT = 9_000
SIDECAR_PORT = 8_900
SIDECAR_BOOT_TIMEOUT = 900  # one cold whole-program compile is ~3 min
NODE_BOOT_TIMEOUT = 300


class LaunchError(Exception):
    pass


def _b64(b: bytes) -> str:
    return base64.standard_b64encode(b).decode()


class Committee:
    """n nodes on loopback, LocalCommittee's port layout: consensus base+i,
    mempool base+n+i, front base+2n+i."""

    def __init__(self, seed: int, n: int) -> None:
        self.n = n
        self.seeds = ref.committee_seeds(seed, n)
        self.pubs = [ref.keypair(s)[0] for s in self.seeds]
        self.names = [_b64(p) for p in self.pubs]

    def consensus(self, i: int) -> str:
        return f"127.0.0.1:{BASE_PORT + i}"

    def front(self, i: int) -> str:
        return f"127.0.0.1:{BASE_PORT + 2 * self.n + i}"

    def write(self, work: str) -> None:
        for i, (name, s) in enumerate(zip(self.names, self.seeds)):
            with open(os.path.join(work, f".node-{i}.json"), "w") as f:
                json.dump({"name": name, "secret": _b64(s)}, f)
        obj = {
            "consensus": {
                "epoch": 1,
                "authorities": {
                    n: {"stake": 1, "address": self.consensus(i)}
                    for i, n in enumerate(self.names)
                },
            },
            "mempool": {
                "epoch": 1,
                "authorities": {
                    n: {
                        "front_address": self.front(i),
                        "mempool_address": f"127.0.0.1:{BASE_PORT + self.n + i}",
                    }
                    for i, n in enumerate(self.names)
                },
            },
        }
        with open(os.path.join(work, ".committee.json"), "w") as f:
            json.dump(obj, f, indent=2, sort_keys=True)


class Deployment:
    """The processes of one run, all in one working directory."""

    def __init__(self, work: str, config: dict, seed: int, fault: str | None = None):
        self.work = work
        self.config = config
        self.n = int(config["nodes"])
        try:
            self.live = arith.live_nodes(config)  # nodes 0 .. live-1 boot
        except ValueError as e:
            raise LaunchError(str(e)) from None
        self.committee = Committee(seed, self.n)
        self.fault = fault
        self.procs: dict[str, subprocess.Popen] = {}
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = ROOT + os.pathsep + self.env.get("PYTHONPATH", "")
        self.env["HOTSTUFF_METRICS_INTERVAL"] = str(config.get("metrics_interval_s", 1))
        self.env.pop("BENCH_RUN", None)

    def log(self, name: str) -> str:
        return os.path.join(self.work, "logs", name + ".log")

    def _spawn(self, name: str, cmd: list[str]) -> subprocess.Popen:
        with open(self.log(name), "w") as out:
            proc = subprocess.Popen(
                cmd, stdout=out, stderr=subprocess.STDOUT, cwd=self.work,
                env=self.env, start_new_session=True,
            )
        self.procs[name] = proc
        return proc

    def start(self, trace_seconds: float | None = None) -> None:
        """Start the sidecar and every node at once. With `trace_seconds` the
        sidecar's shim traces that long when asked (`trace.start`)."""
        os.makedirs(os.path.join(self.work, "logs"), exist_ok=True)
        self.committee.write(self.work)
        with open(os.path.join(self.work, ".parameters.json"), "w") as f:
            json.dump(self.config["parameters"], f, indent=2, sort_keys=True)
        side = self.config["sidecar"]
        cmd = [
            sys.executable, "-m", "chipbench.sidecar_shim", "--control-dir", self.work,
        ]
        if trace_seconds:
            cmd += ["--trace-dir", os.path.join(self.work, "trace"),
                    "--trace-seconds", str(trace_seconds)]
        if self.fault == "skip_half":
            cmd += ["--fault", self.fault]
        cmd += [
            "--", "--port", str(SIDECAR_PORT), "--backend", "tpu",
            "--min-bucket", str(side["min_bucket"]), "--chunk", str(side["chunk"]),
            "--committee", ".committee.json",
        ]
        self._spawn("sidecar", cmd)
        for i in range(self.live):
            module = "hotstuff_tpu.node.main"
            pre: list[str] = []
            if self.fault == "alter_tx" and i == 0:
                module, pre = "chipbench.faulty", ["node"]
            self._spawn(
                f"node-{i}",
                [
                    sys.executable, "-m", module, *pre, "run",
                    "--keys", f".node-{i}.json", "--committee", ".committee.json",
                    "--store", f".db-{i}/log", "--parameters", ".parameters.json",
                    "--crypto", "remote", "--crypto-addr", f"127.0.0.1:{SIDECAR_PORT}",
                ],
            )

    def await_ready(self) -> dict[str, float]:
        """Wait for every process started to say it has booted. Returns the
        seconds each took, from now."""
        t0 = time.time()
        waits = {"sidecar": SIDECAR_BOOT_TIMEOUT}
        waits.update({f"node-{i}": NODE_BOOT_TIMEOUT for i in range(self.live)})
        took: dict[str, float] = {}
        while waits:
            time.sleep(0.25)
            for name in list(waits):
                proc = self.procs[name]
                if proc.poll() is not None:
                    raise LaunchError(
                        f"{name} exited at start-up (rc={proc.returncode}):\n"
                        + tail(self.log(name))
                    )
                with open(self.log(name), errors="replace") as f:
                    if "successfully booted" in f.read():
                        took[name] = time.time() - t0
                        del waits[name]
                        continue
                if time.time() - t0 > waits[name]:
                    raise LaunchError(f"{name} never ready:\n" + tail(self.log(name)))
        return took

    def start_client(self, rate: float, tick_ms: float, seed: int, start: float,
                     stop: float, out: str, name: str = "client") -> subprocess.Popen:
        targets = ",".join(self.committee.front(i) for i in range(self.live))
        return self._spawn(
            name,
            [
                sys.executable, "-m", "chipbench.client", "--targets", targets,
                "--rate", str(rate), "--size", str(self.config["tx_size"]),
                "--tick-ms", str(tick_ms), "--seed", str(seed),
                "--start", repr(start), "--stop", repr(stop), "--out", out,
            ],
        )

    def ask(self, request: str) -> None:
        """Ask the sidecar's shim for something: touch a file it watches."""
        open(os.path.join(self.work, request), "w").close()

    def reply(self, name: str, timeout: float) -> dict | None:
        """The shim's answer (a JSON file in the work directory), or None
        where it has not come by `timeout` or the sidecar has gone."""
        path = os.path.join(self.work, name)
        deadline = time.time() + timeout
        while time.time() < deadline:
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
            if self.procs["sidecar"].poll() is not None:
                return None
            time.sleep(0.1)
        return None

    def stop(self) -> None:
        """SIGTERM every process group, wait, then SIGKILL what is left."""
        for proc in self.procs.values():
            if proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
                except (ProcessLookupError, PermissionError):
                    pass
        deadline = time.time() + 20
        for proc in self.procs.values():
            try:
                proc.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
                proc.wait()


def tail(path: str, lines: int = 25) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError as e:
        return f"(no {path}: {e})"
