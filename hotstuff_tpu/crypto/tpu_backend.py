"""TPU CryptoBackend: the north-star offload.

Routes `Signature::verify_batch` / `verify_batch_alt` equivalents (the
reference's QC::verify path consensus/src/messages.rs:197 and the mempool
batch workload mempool/src/core.rs:135-148) to the JAX ed25519 kernel
(hotstuff_tpu.ops.ed25519), optionally sharded across a device mesh
(hotstuff_tpu.parallel.mesh).

Small batches fall back to the host CPU: the TPU wins only past a crossover
size (dispatch + transfer amortisation — SURVEY.md §7 "hard parts" item 3).
The crossover is configurable.

`register_committee()` installs the validator keys as device-resident
precompute (ops.ed25519.CommitteeTable); batches tagged as committee
traffic whose keys all resolve then ride the committee kernel — no
per-batch key decompression or window-table builds. Untagged batches
(mempool synthetic load, client transactions) keep the generic path.
"""

from __future__ import annotations

import logging
import threading
from typing import Sequence

import numpy as np

from ..utils import metrics
from .backend import CpuBackend, CryptoBackend, columns_to_lists
from .primitives import PublicKey, Signature

log = logging.getLogger("hotstuff.crypto")

# Mirrors the instance-local `stats` dict into the process-global metrics
# registry so backend routing shows up in METRICS snapshots and dumps.
_M_TPU_BATCHES = metrics.counter("crypto.tpu_batches")
_M_TPU_SIGS = metrics.counter("crypto.tpu_sigs")
_M_CPU_BATCHES = metrics.counter("crypto.cpu_batches")
_M_CPU_SIGS = metrics.counter("crypto.cpu_sigs")
_M_BATCH_SIZE = metrics.histogram("crypto.batch_size", metrics.SIZE_BUCKETS)
_M_CROSSOVER_FALLBACKS = metrics.counter("verifier.crossover_fallbacks")
_M_COMMITTEE_MISSES = metrics.counter("verifier.committee_misses")
# Adversarial-rejection visibility: forged/garbage signatures reaching the
# backend show up here (split out for committee-tagged traffic, where a
# rejection means a Byzantine vote/timeout hit the committee kernel's
# rejection lanes). The chaos forged-signature scenarios assert on these.
_M_REJECTED = metrics.counter("verifier.rejected_sigs")
_M_COMMITTEE_REJECTED = metrics.counter("verifier.committee_rejected_sigs")


def _is_decade(count: int) -> bool:
    """True on the 1st, 10th, 100th, ... occurrence — the log-throttling
    rule shared by the crossover-fallback and committee-miss warnings."""
    return count >= 1 and count == 10 ** (len(str(count)) - 1)


class TpuBackend(CryptoBackend):
    name = "tpu"
    # BatchVerificationService probes this to tag committee flushes.
    supports_committee_routing = True
    # A columnar batch (crypto/backend.py `row_columns`) goes through
    # `verify_batch_mask` like any other and stays arrays down to the
    # verifier's staging.
    accepts_columns = True

    def __init__(
        self,
        crossover: int = 64,
        max_bucket: int = 8192,
        min_bucket: int = 128,
        mesh=None,
        sharded: bool = False,
        chunk: int | None = None,
        committee_crossover: int | None = None,
    ):
        # import lazily so CPU-only processes never touch jax
        import jax

        from ..ops import cpu_requested, enable_persistent_cache

        self.cache_dir = enable_persistent_cache()
        # The device is named ONCE, here: everything downstream (the kernel
        # flavour, the sidecar's boot line and exit report, chip_smoke.py)
        # reads these attributes instead of guessing from the class name.
        self.platform = jax.default_backend()
        devices = jax.devices()
        self.device_kind = devices[0].device_kind
        self.device_count = len(devices)
        if self.platform != "tpu" and not cpu_requested():
            raise RuntimeError(
                f"TpuBackend found no TPU (jax backend {self.platform!r}, "
                f"{self.device_kind}); it runs on the CPU only when the "
                "process was told to (JAX_PLATFORMS=cpu, as the tests do)"
            )
        # pallas ladder on the chip; the jnp w4 kernel where the process
        # asked for the CPU (pallas has no CPU lowering). The one place
        # that chooses: the verifier's program table follows from it.
        kernel = "pallas" if self.platform == "tpu" else "w4"
        if sharded or mesh is not None:
            from ..parallel.mesh import ShardedEd25519Verifier

            self._verifier = ShardedEd25519Verifier(
                mesh=mesh,
                min_bucket=min_bucket,
                max_bucket=max_bucket,
                kernel=kernel,
                chunk=chunk,
            )
        else:
            from ..ops.ed25519 import Ed25519TpuVerifier

            self._verifier = Ed25519TpuVerifier(
                min_bucket=min_bucket,
                max_bucket=max_bucket,
                kernel=kernel,
                chunk=chunk,
            )
        log.info(
            "TpuBackend on %s (%s x%d), generic kernel %s, compile cache %s",
            self.platform,
            self.device_kind,
            self.device_count,
            self.kernel_names["generic"],
            self.cache_dir,
        )
        self._cpu = CpuBackend()
        self.crossover = crossover
        # The committee kernel skips per-batch decompression + window-table
        # builds and ships 96 B + a 4 B index (vs 128 B) per signature, so
        # its device break-even sits well below the generic crossover.
        # Default crossover/4 so quorum-sized QC/TC batches (2f+1 votes)
        # actually ride the device-resident tables instead of falling to
        # the host CPU.
        if committee_crossover is not None:
            self.committee_crossover = committee_crossover
        else:
            self.committee_crossover = max(1, crossover // 4)
            # Mesh-aware floor: a sharded verifier's narrowest bucket is
            # lane * ndev (mesh_alignment), so a sub-alignment quorum batch
            # pads up to a FULL mesh bucket — the device pays align lanes
            # regardless of occupancy and the break-even scales with the
            # inflation. Keep the single-chip ratio (crossover/4 = 16
            # against min_bucket 128, i.e. min_bucket/8).
            align = getattr(self._verifier, "mesh_alignment", 0)
            if align:
                self.committee_crossover = max(
                    self.committee_crossover, align // 8
                )
        self._lock = threading.Lock()
        self.stats = {"tpu_batches": 0, "tpu_sigs": 0, "cpu_batches": 0, "cpu_sigs": 0}

    def close(self) -> None:
        """Drain the verifier's dispatch-pipeline workers (ops/pipeline.py).
        Optional — dropped backends are reaped by GC/atexit — but a tidy
        shutdown path for tests and per-shard steal backends."""
        closer = getattr(self._verifier, "close", None)
        if closer is not None:
            closer()

    @property
    def kernel_names(self) -> dict[str, str]:
        """The program each family dispatches 32-byte-digest batches to."""
        v = self._verifier
        return {
            "generic": v.program_name(False, True),
            "committee": v.program_name(True, True),
        }

    def report(self) -> dict:
        """What ran where: the device as JAX named it, the routing stats and
        the chunks dispatched per program. The sidecar publishes this in its
        METRICS line (`info.backend`), which is how a served run shows that
        the device — not a node's CPU — checked signatures."""
        with self._lock:
            stats = dict(self.stats)
        return {
            "platform": self.platform,
            "device_kind": self.device_kind,
            "device_count": self.device_count,
            "kernels": self.kernel_names,
            "dispatched": dict(self._verifier.dispatched),
            **stats,
        }

    @property
    def bucket_alignment(self) -> int:
        """The device bucket grid: `lane * ndev` on a mesh
        (parallel/mesh.py `mesh_alignment`), the narrowest bucket width on
        a single chip. The continuous-batching scheduler
        (crypto/scheduler.py) sizes bulk buckets against this so a closed
        bucket pads zero lanes; gridless backends (CPU, pure-python)
        simply lack the attribute."""
        v = self._verifier
        return getattr(v, "mesh_alignment", 0) or getattr(v, "min_bucket", 0)

    # -- committee registration ---------------------------------------------

    def register_committee(
        self, keys: Sequence[PublicKey | bytes], warmup: bool = False
    ) -> int:
        """Install the committee keys as device-resident precompute.

        Idempotent for an identical key sequence; a CHANGED key set (epoch
        reconfiguration) invalidates and rebuilds the table. With `warmup`,
        force-compiles the committee kernel at every bucket width the
        dispatcher uses (same rationale as `warmup()`). Returns the
        committee size."""
        raw = [k.data if isinstance(k, PublicKey) else bytes(k) for k in keys]
        if not getattr(self._verifier, "supports_committee", False):
            log.warning(
                "committee registration skipped: %s has no committee path",
                type(self._verifier).__name__,
            )
            return 0
        table = self._verifier.set_committee(raw)
        log.info(
            "registered %d-key committee for device-resident verification",
            table.size,
        )
        if warmup:
            self._warmup_committee()
        return table.size

    def _warmup_widths(self) -> list[int]:
        """Batch sizes that, fed through the dispatcher, compile every
        bucket width it can dispatch at runtime — shared by warmup() and
        _warmup_committee() so the two kernel families are compiled at
        exactly the same shapes.

        Each candidate size is mapped through the verifier's OWN bucketing
        and deduplicated on the resulting width: mesh alignment
        (min_bucket = lane * ndev, max_bucket rounded to the alignment
        grid) and pallas BLOCK rounding can collapse ladder steps onto one
        dispatched width, and emitting the raw power-of-two ladder would
        compile shapes the sharded verifier re-buckets and never
        dispatches. Sizes are capped at the chunk so every warmup batch
        dispatches as exactly one chunk (no stray split-remainder shapes).
        """
        v = self._verifier
        top = min(v.chunk, v.max_bucket) if hasattr(v, "chunk") else v.max_bucket
        sizes, w = [], v.min_bucket
        while w < top:
            sizes.append(w)
            w *= 2
        # The full-chunk dispatch (its bucket may exceed `top` when
        # min_bucket isn't a power of two).
        sizes.append(top)
        seen, out = set(), []
        for n in sizes:
            width = v._bucket(n)
            if width not in seen:
                seen.add(width)
                out.append(n)
        return out

    def _warmup_committee(self) -> float:
        """Compile the committee kernel family at every dispatch bucket
        width (junk wire bytes; shapes are all that matter — see
        `warmup()`). Returns wall seconds spent."""
        import os
        import time

        t0 = time.perf_counter()
        v = self._verifier
        sizes = self._warmup_widths()
        for n in sizes:
            v.verify_batch_mask_committee(
                [os.urandom(32)] * n, [0] * n, [os.urandom(64)] * n
            )
        secs = time.perf_counter() - t0
        log.info(
            "committee kernel warmup: %d batch sizes (widths %s) in %.1f s",
            len(sizes),
            [v._bucket(n) for n in sizes],
            secs,
        )
        return secs

    def warmup(self) -> float:
        """Force-compile every device bucket shape the verifier dispatches at
        runtime, BEFORE the node joins consensus. The first dispatch at each
        bucket width triggers XLA compilation (minutes cold for one whole
        verify program); paying that lazily inside the protocol stalls
        rounds past timeout_delay and fires the pacemaker (the round-4
        saturation runs logged dozens of boot-window timeouts). With the persistent compile cache enabled in
        __init__, later processes and runs hit the on-disk cache and skip
        the compile (trace, lower and load remain: about a minute per
        program on a v5e host). Returns wall seconds spent.

        Junk inputs are used on purpose: compilation is shape-dependent
        only, and the masks are discarded. 32-byte messages warm the
        production device-hash program. The host-hash twin (messages of
        any other length) is NOT compiled here: one whole verify program
        is minutes of compile and a quarter of a gigabyte of code, the
        protocol signs 32-byte digests, and nothing reruns a failed
        device-hash batch through it — a program the chip's compiler
        refuses raises here, before the process serves anything.
        """
        import os
        import time

        t0 = time.perf_counter()
        v = self._verifier
        sizes = self._warmup_widths()
        for n in sizes:
            junk_m = [os.urandom(32)] * n
            junk_k = [os.urandom(32)] * n
            junk_s = [os.urandom(64)] * n
            v.verify_batch_mask(junk_m, junk_k, junk_s)
        secs = time.perf_counter() - t0
        log.info(
            "generic kernel warmup: %d batch sizes (widths %s) in %.1f s",
            len(sizes),
            [v._bucket(n) for n in sizes],
            secs,
        )
        return secs

    def verify_batch_mask(
        self,
        messages: Sequence[bytes],
        keys: Sequence[PublicKey],
        signatures: Sequence[Signature],
        committee: bool = False,
    ) -> list[bool]:
        """`committee=True` marks the batch as consensus traffic signed by
        registered validator keys: indices are resolved against the
        registered table, the lower `committee_crossover` governs the CPU
        fallback, and the batch rides the committee kernel. Batches with
        any unregistered key (or no registration) fall back to the generic
        path — correctness never depends on the tag.

        The three arguments may be the uint8 column arrays of one columnar
        batch; above the crossover they reach the verifier as they are and
        the mask comes back as a bool array. The committee table and the
        host's OpenSSL want objects."""
        n = len(messages)
        if n == 0:
            return []
        _M_BATCH_SIZE.record(n)
        columnar = isinstance(messages, np.ndarray)
        if columnar and (committee or n < self.crossover):
            messages, keys, signatures = columns_to_lists(
                messages, keys, signatures
            )
            columnar = False
        # Resolve committee routing BEFORE the crossover decision: the
        # committee kernel's cheaper per-batch cost earns it a lower
        # CPU/device break-even than the generic path.
        resolved = self._resolve_committee(keys) if committee else None
        threshold = (
            self.committee_crossover if resolved is not None else self.crossover
        )
        if n < threshold:
            with self._lock:
                self.stats["cpu_batches"] += 1
                self.stats["cpu_sigs"] += n
            _M_CPU_BATCHES.inc()
            _M_CPU_SIGS.inc(n)
            _M_CROSSOVER_FALLBACKS.inc()
            # Log once per decade of fallback count (1st, 10th, 100th, ...)
            # so bench runs show how often the TPU path is bypassed without
            # flooding the log at consensus rates.
            count = _M_CROSSOVER_FALLBACKS.value
            if _is_decade(count):
                log.info(
                    "sub-crossover fallback #%d: batch of %d < crossover %d "
                    "verified on host CPU",
                    count,
                    n,
                    threshold,
                )
            mask = self._cpu.verify_batch_mask(messages, keys, signatures)
            self._count_rejections(mask, resolved is not None)
            return mask
        with self._lock:
            self.stats["tpu_batches"] += 1
            self.stats["tpu_sigs"] += n
        _M_TPU_BATCHES.inc()
        _M_TPU_SIGS.inc(n)
        if resolved is not None:
            indices, table = resolved
            # the table is PINNED through the dispatch: a concurrent
            # re-registration must not swap it under these indices
            mask = self._verifier.verify_batch_mask_committee(
                list(messages),
                indices,
                [s.data for s in signatures],
                table=table,
            ).tolist()
            self._count_rejections(mask, True)
            return mask
        if columnar:
            mask = self._verifier.verify_batch_mask(messages, keys, signatures)
        else:
            mask = self._verifier.verify_batch_mask(
                list(messages),
                [k.data for k in keys],
                [s.data for s in signatures],
            ).tolist()
        self._count_rejections(mask, False)
        return mask

    @staticmethod
    def _count_rejections(mask: Sequence[bool], committee: bool) -> None:
        bad = len(mask) - int(np.count_nonzero(mask))
        if bad:
            _M_REJECTED.inc(bad)
            if committee:
                _M_COMMITTEE_REJECTED.inc(bad)

    def _resolve_committee(self, keys: Sequence[PublicKey]):
        """Map keys to validator indices against ONE table snapshot;
        returns (indices, table), or None when unroutable (no
        registration, or any key outside the registered set)."""
        table = getattr(self._verifier, "committee", None)
        if table is None:
            return None
        try:
            return [table.index[k.data] for k in keys], table
        except KeyError:
            _M_COMMITTEE_MISSES.inc()
            # Once per decade of misses, mirroring crossover_fallbacks:
            # persistent misses mean the registered table is stale (epoch
            # reconfiguration without re-registering) and committee
            # traffic is silently riding the generic kernel.
            count = _M_COMMITTEE_MISSES.value
            if _is_decade(count):
                log.info(
                    "committee miss #%d: tagged batch of %d contains "
                    "unregistered key(s); falling back to the generic "
                    "kernel (re-register after reconfiguration?)",
                    count,
                    len(keys),
                )
            return None
