"""Driver for Tucker decompositions by ``repro.core.tucker.hooi``
(``kind: tucker``).

Set-up makes the seeded tensor on the device and runs one whole
decomposition, which compiles every program the window uses.  The window
runs ``hooi()`` as users call it, job after job on the resident tensor,
each to its end (core, factors and error on the device); the window
closes when the last job started before ``seconds`` ends.  Each job sits
inside a host annotation ``bench.hooi``.

``correct`` compares every job's core, factors and error with a plain
float32 HOOI (``bench/reference/hooi.py``) at ``precision="highest"``.
The number held to a limit is ``core_rel``: the program's core, turned
into the reference's factor bases (G x1 A_ref^T A x2 ...), against the
reference core, relative; a factor off the reference's subspace moves
it.  Two more are read and printed without a limit, since the control
does not move them to three times the program's reading (PERF.md):
``subspace_sin``, the largest sine of a principal angle between a
program factor and the reference's, and ``error_rel``, the relative
difference of the two reconstruction errors.

With ``control`` the window runs the control in the program's place:
the reference itself at ``precision="high"`` (three bf16 passes on a
TPU), the step below the configuration's ``highest``.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from bench.generators import jax_key
from bench.reference import hooi as ref

__all__ = ["setup", "measure", "check", "free", "attempted_failed"]

#: the limit, between the program's largest reading on a v5e (about
#: 1.0e-6) and the smallest of the control at precision="high"
#: (2.31e-5), with more room above the former (PERF.md)
LIMITS = {"core_rel": 5e-6}
READ = ("subspace_sin", "error_rel")


class Tucker:
    def __init__(self, conf: dict, seed: int, control: bool):
        self.conf, self.control = conf, control
        t = conf["tensor"]
        self.T = ref.make_tensor(conf["n"], t["rank"], t["noise_to_tail"],
                                jax_key(seed))
        self.T.block_until_ready()
        self.results: list = []

    def job(self):
        """One decomposition, as users call it, waited for: (core,
        factors, relative error)."""
        from repro.core.tucker import hooi

        c = self.conf
        with jax.profiler.TraceAnnotation("bench.hooi"), \
                jax.default_matmul_precision(c["precision"]):
            if self.control:
                out = reference(self, "high")
            else:
                r = hooi(self.T, tuple(c["ranks"]), n_iter=c["n_iter"],
                         strategy=c["strategy"], backend=c["backend"])
                out = (r.core, r.factors, r.rel_error)
            jax.block_until_ready(out)
        return out


def setup(conf: dict, work, seed: int, control: bool = False) -> Tucker:
    del work
    sut = Tucker(conf, seed, control)
    sut.job()
    sut.warmed = 1
    return sut


def measure(sut: Tucker, seconds: float, tracer) -> dict:
    t0 = time.perf_counter()
    tracer.arm(t0)
    ends = []
    while time.perf_counter() - t0 < seconds:
        tracer.poll()
        sut.results.append(sut.job())
        ends.append(time.perf_counter() - t0)
    tracer.finish()
    return {"window_s": ends[-1], "jobs": len(ends), "ends": ends,
            "conf": sut.conf}


def attempted_failed(rec: dict) -> tuple[int, int]:
    return rec["jobs"], 0


def free(sut: Tucker) -> None:
    import gc

    gc.collect()


def _compare(got, want) -> dict:
    """The three numbers of one job against the reference."""
    (G, fac, err), (Gr, facr, errr) = (
        (np.asarray(x[0], np.float64),
         [np.asarray(f, np.float64) for f in x[1]], float(x[2]))
        for x in (got, want))
    sines, turns = [], []
    for U, Ur in zip(fac, facr):
        M = Ur.T @ U                                  # r x r
        sines.append(np.linalg.norm(U - Ur @ M, 2))
        turns.append(M)
    Ga = np.einsum("ijk,ai,bj,ck->abc", G, *turns)
    return {"subspace_sin": max(sines),
            "core_rel": np.linalg.norm(Ga - Gr) / np.linalg.norm(Gr),
            "error_rel": abs(err - errr) / errr}


def reference(sut: Tucker, precision: str):
    c = sut.conf
    n = c["n"]
    return ref.hooi(sut.T, ranks=tuple(c["ranks"]), n_iter=c["n_iter"],
                    precision=precision, slab=64 if n % 64 == 0 else n)


def check(sut: Tucker, rec: dict, seed: int) -> dict:
    del rec, seed
    want = jax.device_get(reference(sut, "highest"))
    worst = dict.fromkeys((*LIMITS, *READ), 0.0)
    for got in sut.results:
        for k, v in _compare(jax.device_get(got), want).items():
            worst[k] = max(worst[k], float(v))
    readings = {k: worst[k] for k in READ}
    checks = [(k, worst[k], LIMITS[k], worst[k] <= LIMITS[k]) for k in LIMITS]
    checks.append(("jobs_compared", len(sut.results), 1,
                   len(sut.results) >= 1))
    return {"checks": checks, "readings": readings}
