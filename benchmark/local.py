"""Local benchmark runner (reference benchmark/benchmark/local.py:37-120).

Boots a committee of node processes plus one client per node on localhost,
runs for `duration` seconds, kills everything, and parses the logs. The
reference manages processes with tmux; here plain subprocesses with per-process
log redirection (logs/node-i.log, logs/client-i.log) serve the same role.
"""

from __future__ import annotations

import os
import shlex
import signal
import subprocess
import time
from os.path import join

from .commands import CommandMaker
from .config import BenchParameters, LocalCommittee, NodeParameters
from .logs import LogParser, ParseError


class BenchError(Exception):
    pass


class LocalBench:
    BASE_PORT = 9_000
    # One cold whole-program compile (~180 s measured on a v5e host) plus
    # device init, with room for a slower host.
    SIDECAR_BOOT_TIMEOUT = 600

    def __init__(self, bench_params: dict, node_params: dict) -> None:
        self.bench = BenchParameters(bench_params)
        self.node_params = NodeParameters(node_params)
        self.crypto = bench_params.get("crypto", "cpu")
        # Sidecar pipeline chunk override (device chunk sweep's verdict);
        # None = verifier default.
        self.sidecar_chunk = bench_params.get("sidecar_chunk")
        # Narrowest sidecar bucket; None = CommandMaker.run_sidecar's
        # default (one generic width at the default chunk).
        self.sidecar_min_bucket = bench_params.get("sidecar_min_bucket")
        self._procs: list[subprocess.Popen] = []

    def _background_run(self, command: str, log_file: str) -> subprocess.Popen:
        with open(log_file, "w") as out:
            proc = subprocess.Popen(
                shlex.split(command),
                stdout=out,
                stderr=subprocess.STDOUT,
                cwd=os.getcwd(),
                start_new_session=True,
            )
        self._procs.append(proc)
        return proc

    @staticmethod
    def _await_in_logs(waits, phrase: str, timeout: float, what: str) -> None:
        """Block until every (log_path, proc) in `waits` has `phrase` in its
        log. Fails fast with the real exit code when a process dies during
        startup instead of burning the timeout on a log line that can never
        appear."""
        deadline = time.monotonic() + timeout
        pending = dict(waits)
        while pending and time.monotonic() < deadline:
            time.sleep(0.5)
            for path, proc in list(pending.items()):
                if proc.poll() is not None:
                    raise BenchError(
                        f"{what} exited at startup "
                        f"(rc={proc.returncode}); see {path}"
                    )
                try:
                    with open(path) as f:
                        if phrase in f.read():
                            del pending[path]
                except OSError:
                    pass
        if pending:
            raise BenchError(f"{what} never ready: {sorted(pending)}")

    def _kill(self) -> None:
        for proc in self._procs:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                pass
        self._procs.clear()

    def run(self, debug: bool = False) -> LogParser:
        nodes = self.bench.nodes[0]
        rate = self.bench.rate[0]
        faults = self.bench.faults
        boot = nodes - faults

        print(f"Running local benchmark: {nodes} nodes ({faults} faults), "
              f"{rate} tx/s, {self.bench.tx_size} B txs, {self.bench.duration} s, "
              f"crypto={self.crypto}")
        subprocess.run(CommandMaker.kill(), shell=True, capture_output=True)
        subprocess.run(CommandMaker.cleanup(), shell=True, check=True)
        subprocess.run(CommandMaker.clean_logs(), shell=True, check=True)

        try:
            # Generate keys and committee (in-process: one interpreter launch
            # per key is prohibitively slow on small boxes).
            from hotstuff_tpu.node.config import Secret

            key_files = [f".node-{i}.json" for i in range(nodes)]
            names = []
            for f in key_files:
                secret = Secret.new()
                secret.write(f)
                names.append(secret.name.encode_base64())
            committee = LocalCommittee(names, self.BASE_PORT)
            committee.write(".committee.json")
            self.node_params.write(".parameters.json")

            # TPU crypto: boot ONE sidecar process owning the chip; nodes
            # connect as remote clients (the TPU is process-exclusive).
            node_crypto, crypto_addr = self.crypto, None
            if self.crypto == "tpu":
                sidecar_port = self.BASE_PORT - 100
                crypto_addr = f"127.0.0.1:{sidecar_port}"
                sidecar_proc = self._background_run(
                    CommandMaker.run_sidecar(
                        sidecar_port,
                        "tpu",
                        debug=debug,
                        chunk=self.sidecar_chunk,
                        committee=".committee.json",
                        min_bucket=self.sidecar_min_bucket,
                    ),
                    join("logs", "sidecar.log"),
                )
                # Device init + warm-up of the ONE generic width the
                # sidecar dispatches (CommandMaker.run_sidecar): a cold
                # compile of that program is ~3 min on a v5e host, ~70 s
                # from the persistent cache. The wait ends at once if the
                # sidecar dies (_await_in_logs).
                self._await_in_logs(
                    [(join("logs", "sidecar.log"), sidecar_proc)],
                    "successfully booted",
                    self.SIDECAR_BOOT_TIMEOUT,
                    "crypto sidecar",
                )
                node_crypto = "remote"

            # Boot nodes (skipping `faults` of them -- fault injection by
            # simply not booting, local.py:75-76).
            node_waits = []
            for i in range(boot):
                cmd = CommandMaker.run_node(
                    key_files[i],
                    ".committee.json",
                    f".db-{i}/log",
                    ".parameters.json",
                    crypto=node_crypto,
                    crypto_addr=crypto_addr,
                    debug=debug,
                )
                log_path = CommandMaker.logs_path("logs", "node", i)
                node_waits.append((log_path, self._background_run(cmd, log_path)))

            # Wait until every node reports booted: Python interpreter
            # startup under CPU contention can take ~10 s on small machines,
            # and killing before boot would measure nothing. The timeout
            # scales with committee size (2n processes share one core).
            self._await_in_logs(
                node_waits, "successfully booted", 90 + 6 * boot, "node"
            )

            # One client per booted node.
            per_client_rate = max(1, rate // boot)
            consensus_addrs = [
                committee.consensus_addr[n] for n in names[:boot]
            ]
            client_waits = []
            for i in range(boot):
                cmd = CommandMaker.run_client(
                    committee.front_addr[names[i]],
                    self.bench.tx_size,
                    per_client_rate,
                    consensus_addrs,
                )
                log_path = CommandMaker.logs_path("logs", "client", i)
                client_waits.append(
                    (log_path, self._background_run(cmd, log_path))
                )

            # Wait until every client is actually sending before starting
            # the measurement clock: at 2 processes per node on one core,
            # the last client interpreters can take >60 s to start (at
            # n=20 the entire 60 s window used to elapse with zero
            # transactions sent — blocks committed empty and the run
            # parsed as a zero-TPS "cliff" that was purely boot skew).
            # LogParser additionally starts its steady-state window at the
            # LAST client's first send, so any residual skew stays out of
            # the throughput denominator.
            self._await_in_logs(
                client_waits,
                "Start sending transactions",
                90 + 6 * boot,
                "client",
            )

            time.sleep(self.bench.duration)
            self._kill()
            time.sleep(0.5)
            return LogParser.process("logs", faults)
        except (subprocess.SubprocessError, ParseError, OSError) as e:
            self._kill()
            raise BenchError(f"local benchmark failed: {e}") from e
        finally:
            self._kill()
