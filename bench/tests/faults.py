"""Faults planted under a rehearsal run, for ``test_bench_rehearsal.py``.

``install(name)`` wraps the call that produces the answers of the timed
path: ``repro.core.execute`` for the product cells and
``repro.distributed.spgemm_shard.execute_sharded`` for the service.

- ``altered``: one answer's largest value off by one part in a thousand;
- ``unchanged``: every call returns the first answer it gave, as a step
  that leaves its state unchanged;
- ``half``: half of the work left out, the rest answered: the second
  half of a product's rows empty, or a flush's second half of lanes
  answered with the first half's results;
- ``control``: the bfloat16 control (``control.py``) in the program's
  place.
"""
from __future__ import annotations

import numpy as np


def _altered(indptr, indices, data):
    n = int(indptr[-1])
    data = data.copy()
    j = int(np.argmax(np.abs(data[:n])))
    data[j] *= 1 + 1e-3
    return indptr, indices, data


def _half(indptr, indices, data):
    indptr = indptr.copy()
    h = (len(indptr) - 1) // 2
    indptr[h + 1:] = indptr[h]
    return indptr, indices, data


def _host(m):
    return tuple(np.asarray(x) for x in (m.indptr, m.indices, m.data))


def install(name: str) -> None:
    import jax.numpy as jnp
    from repro import core
    from repro.core import dispatch
    from repro.core.formats import BatchedCSR, CSR
    from repro.distributed import spgemm_shard

    import control

    first = {}

    def product(real):
        def execute(p, A, B, **kw):
            out = real(p, A, B, **kw)
            if name == "unchanged":
                return first.setdefault("out", out)
            if name == "control":
                arrs = control.bf16_product(*_host(A), A.shape)
            else:
                arrs = {"altered": _altered, "half": _half}[name](*_host(out))
            return CSR(*map(jnp.asarray, arrs), shape=out.shape)
        return execute

    def sharded(real):
        def execute_sharded(sp, A, B):
            out = real(sp, A, B)
            if name == "unchanged":
                return first.setdefault("out", out)
            lanes = [_host(out[i]) for i in range(out.batch)]
            if name == "altered":
                lanes[0] = _altered(*lanes[0])
            elif name == "half":
                h = out.batch // 2
                lanes[h:] = lanes[:out.batch - h]
            elif name == "control":
                lanes = [control.bf16_product(*_host(A[i]), A.shape)
                         for i in range(out.batch)]
            cap = max(int(ip[-1]) for ip, _, _ in lanes)
            ix = np.zeros((out.batch, cap), np.int32)
            dt = np.zeros((out.batch, cap), np.float32)
            for i, (ip, ind, d) in enumerate(lanes):
                ix[i, :ip[-1]] = ind[:ip[-1]]
                dt[i, :ip[-1]] = d[:ip[-1]]
            return BatchedCSR(jnp.asarray(np.stack([ip for ip, _, _ in lanes])),
                              jnp.asarray(ix), jnp.asarray(dt), out.valid,
                              out.shape)
        return execute_sharded

    core.execute = product(dispatch.execute)
    spgemm_shard.execute_sharded = sharded(spgemm_shard.execute_sharded)
