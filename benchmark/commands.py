"""Canonical shell command strings (reference benchmark/benchmark/commands.py:6-56).

The reference aliases compiled Rust binaries; here the "binaries" are the
package entry points run with the current interpreter.
"""

from __future__ import annotations

import sys
from os.path import join


class CommandMaker:
    @staticmethod
    def cleanup() -> str:
        return "rm -rf .db-* ; rm -f .*.json ; mkdir -p logs"

    @staticmethod
    def clean_logs() -> str:
        return "rm -rf logs ; mkdir -p logs"

    @staticmethod
    def compile() -> str:
        # No compilation for the Python path; the native plane builds via make.
        return f"{sys.executable} -c 'import hotstuff_tpu'"

    @staticmethod
    def generate_key(filename: str) -> str:
        return f"{sys.executable} -m hotstuff_tpu.node.main keys --filename {filename}"

    @staticmethod
    def run_node(keys: str, committee: str, store: str, parameters: str, crypto: str = "cpu", crypto_addr: str | None = None, debug: bool = False) -> str:
        v = "-vvv" if debug else "-vv"
        addr = f" --crypto-addr {crypto_addr}" if crypto_addr else ""
        return (
            f"{sys.executable} -m hotstuff_tpu.node.main {v} run "
            f"--keys {keys} --committee {committee} --store {store} "
            f"--parameters {parameters} --crypto {crypto}{addr}"
        )

    @staticmethod
    def run_sidecar(
        port: int,
        backend: str = "tpu",
        debug: bool = False,
        chunk: int | None = None,
        committee: str | None = None,
        min_bucket: int | None = None,
    ) -> str:
        """The shared crypto sidecar: one process owns the TPU; all local
        nodes ship their large verification batches to it. `committee`
        points at the node committee file so the sidecar holds the
        validator tables on the device. `min_bucket` defaults to 4096,
        which with the default 4096 chunk makes the sidecar dispatch ONE
        generic width: each width is a whole verify program, minutes of
        cold compile."""
        v = "-vvv" if debug else "-vv"
        chunk_arg = f" --chunk {chunk}" if chunk is not None else ""
        committee_arg = f" --committee {committee}" if committee else ""
        bucket_arg = (
            f" --min-bucket {4096 if min_bucket is None else min_bucket}"
            if backend == "tpu"
            else ""
        )
        return (
            f"{sys.executable} -m hotstuff_tpu.crypto.remote {v} "
            f"--port {port} --backend {backend}{bucket_arg}{chunk_arg}"
            f"{committee_arg}"
        )

    @staticmethod
    def run_client(address: str, size: int, rate: int, nodes: list[str], duration: float | None = None) -> str:
        nodes_arg = f" --nodes {' '.join(nodes)}" if nodes else ""
        dur = f" --duration {duration}" if duration is not None else ""
        return (
            f"{sys.executable} -m hotstuff_tpu.node.client -vv {address} "
            f"--size {size} --rate {rate}{nodes_arg}{dur}"
        )

    @staticmethod
    def kill() -> str:
        # covers node, client, AND the crypto sidecar (hotstuff_tpu.crypto.remote)
        return "pkill -f 'hotstuff_tpu.node' ; pkill -f 'hotstuff_tpu.crypto.remote' || true"

    @staticmethod
    def logs_path(directory: str, kind: str, i: int) -> str:
        return join(directory, f"{kind}-{i}.log")
