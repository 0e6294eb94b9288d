"""The one traffic generator: builds a cell's operands from the seed and
drives them through the program in a closed loop.

A traffic mix is a JSON file of parameters under ``bench/traffic/``:

- ``entry``: ``"product"`` (each operation is ``plan`` then ``execute``
  of ``A @ A``, which is what ``repro.core.spgemm`` does) or
  ``"service"`` (each round submits ``callers`` requests to one
  in-process ``SpGemmService`` and waits for their answers);
- ``callers``: operations per round (1 for ``product``);
- ``patterns``, ``value_sets``: the operand pool, ``patterns`` matrices
  of the configuration's class, each with ``value_sets`` sets of values
  on its one pattern; operation ``i`` takes pool entry ``i`` modulo the
  pool's size, value-set-major (every pattern with its first value set,
  then every pattern with the next); the warm-up takes the last value
  set, so the window's first operations are not the warm-up's;
- ``max_batch``, ``flush_timeout``: the service's lanes per flush and
  the seconds a partly filled bucket may wait before ``pump`` flushes it
  (``service`` only).
"""
from __future__ import annotations

import dataclasses
import sys
import time
import traceback

# the benchmark's host spans, written into the profiler's trace while it
# runs: bench.window, bench.product, bench.plan, bench.execute,
# bench.round, bench.submit, bench.pump
from jax.profiler import TraceAnnotation as span

from gen import matrices


@dataclasses.dataclass
class Op:
    """One operation of the window: a product, or one request."""
    pool_index: int
    plan_s: float | None = None
    output: object = None     # the CSR the caller received
    error: str | None = None


@dataclasses.dataclass
class Run:
    """What one run recorded, which the metric readers read."""
    setup_s: float = 0.0
    window_s: float = 0.0
    ops: list = dataclasses.field(default_factory=list)
    flushes: list = dataclasses.field(default_factory=list)
    plans: set = dataclasses.field(default_factory=set)
    trace: object = None          # trace_reduce.Reduction, with --trace 1
    program: object = None        # program_trace.ProgramReduction, idem
    # after the check, of each completed operation: its operand's host
    # (indptr, indices) and the reference product's nnz
    operands: list = dataclasses.field(default_factory=list)
    least_bytes: float | None = None   # mean over the window's operations
    least_flops: float | None = None
    peak: dict | None = None

    @property
    def done(self) -> list:
        return [op for op in self.ops if op.error is None]


def host_pool(config: dict, traffic: dict, seed: int, rows: int) -> list:
    """The operand pool's host arrays, ``(indptr, indices, data)`` per
    entry, value-set-major; the value sets of a pattern share its index
    arrays."""
    built = [matrices.build(config, rows, seed, p, traffic["value_sets"])
             for p in range(traffic["patterns"])]
    return [(indptr, indices, values[v])
            for v in range(traffic["value_sets"])
            for indptr, indices, values in built]


class Pool:
    """The operand pool: host arrays for the reference, device arrays
    handed to the program through its public ``CSR`` constructor.  The
    value sets of one pattern share one device index array, as a caller
    who keeps its pattern would."""

    def __init__(self, config: dict, traffic: dict, seed: int, rows: int):
        import jax.numpy as jnp
        self.shape = (rows, rows)
        self.patterns = traffic["patterns"]
        self.host = host_pool(config, traffic, seed, rows)
        self._dev = []       # (indptr, indices, data) device arrays
        dev_index = {}
        for indptr, indices, data in self.host:
            if id(indices) not in dev_index:
                dev_index[id(indices)] = (jnp.asarray(indptr),
                                          jnp.asarray(indices))
            self._dev.append((*dev_index[id(indices)], jnp.asarray(data)))

    def __len__(self) -> int:
        return len(self.host)

    def warm_rounds(self, callers: int) -> list[list[int]]:
        """Rounds that touch every pattern once, with its last value
        set: the shapes a round compiles for depend on the patterns, not
        on the values."""
        last = list(range(len(self) - self.patterns, len(self)))
        n = -(-len(last) // callers) * callers
        last = [last[j % len(last)] for j in range(n)]
        return [last[j:j + callers] for j in range(0, n, callers)]

    def round(self, i: int, callers: int) -> list[int]:
        """The pool entries of the window's round ``i``."""
        return [(i * callers + c) % len(self) for c in range(callers)]

    def csr(self, k: int):
        """A new ``CSR`` object on pool entry ``k``'s arrays, as a caller
        with a new value set on a known pattern would build one."""
        from repro.core import CSR
        return CSR(*self._dev[k], shape=self.shape)

    def release(self) -> None:
        self._dev = []


def _block(csr) -> None:
    import jax
    jax.block_until_ready((csr.indptr, csr.indices, csr.data))


class ProductEntry:
    """``plan`` then ``execute`` of ``A @ A``: one product per round."""

    def __init__(self, traffic: dict, pool: Pool, run: Run, backend: str):
        if traffic["callers"] != 1:
            raise ValueError("the product entry has one caller")
        self.pool, self.run = pool, run
        self.backend = backend

    def round(self, ks: list[int]) -> list[Op]:
        from repro import core
        k, = ks
        A = self.pool.csr(k)
        op = Op(k)
        try:
            with span("bench.product"):
                t0 = time.perf_counter()
                with span("bench.plan"):
                    p = core.plan(A, A, backend=self.backend)
                op.plan_s = time.perf_counter() - t0
                with span("bench.execute"):
                    op.output = core.execute(p, A, A)
                    _block(op.output)
            self.run.plans.add((p.engine, p.backend, p.source))
        except Exception:  # the run goes on; the operation counts failed
            op.error = traceback.format_exc()
        return [op]

    def close(self) -> None:
        pass


class ServiceEntry:
    """``callers`` requests per round through one ``SpGemmService``,
    pumped as a serving loop pumps it: a bucket flushes when it fills,
    or when ``pump`` finds its oldest request older than the timeout."""

    def __init__(self, traffic: dict, pool: Pool, run: Run, backend: str):
        from repro.serving.spgemm_service import SpGemmService
        self.pool, self.run = pool, run
        self.svc = SpGemmService(max_batch=traffic["max_batch"],
                                 flush_timeout=traffic["flush_timeout"],
                                 engine="auto")

    def round(self, ks: list[int]) -> list[Op]:
        ops, n_flushes = [], len(self.svc.flush_log)
        with span("bench.round"):
            with span("bench.submit"):
                reqs = [(k, self.svc.submit(A, A))
                        for k, A in ((k, self.pool.csr(k)) for k in ks)]
            with span("bench.pump"):
                while self.svc.pending:
                    self.svc.pump()
                    time.sleep(self.svc.flush_timeout / 10)
                for _, r in reqs:
                    if r.result is not None:
                        _block(r.result)
        for k, r in reqs:
            op = Op(k, output=r.result)
            if r.error is not None:
                op.error = str(r.error)
            elif r.tier != "planned":
                op.error = f"answered by the degraded tier {r.tier!r}"
            ops.append(op)
        for f in self.svc.flush_log[n_flushes:]:
            self.run.plans.add((f.engine, None, f.source))
            self.run.flushes.append(f)
        return ops

    def close(self) -> None:
        self.svc.close()
        self.svc = None


ENTRIES = {"product": ProductEntry, "service": ServiceEntry}


def window(entry, pool: Pool, callers: int,
           seconds: float) -> tuple[list, float, list]:
    """Start rounds while less than ``seconds`` has passed; the window
    ends when the last round started has completed.  Returns the
    operations, the window's length and each round's length."""
    ops, rounds = [], []
    t0 = time.perf_counter()
    with span("bench.window"):
        while (t := time.perf_counter()) - t0 < seconds:
            ops.extend(entry.round(pool.round(len(rounds), callers)))
            rounds.append(time.perf_counter() - t)
    return ops, time.perf_counter() - t0, rounds


def log(msg: str) -> None:
    print(msg, flush=True)


def warn(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
