"""Per cent of the roofline the Pallas contractions reach: the summed
unpadded least times of the decompositions' contractions that run as
Pallas kernels (each sweep's five non-Gram products, the core's three
and the reconstruction's three; bench/counts.py), for the whole jobs in
the traced window, over the device time of the Pallas kernels in it
(trace)."""

from bench import counts

#: pairwise products hooi() runs through the Pallas backend; the Gram
#: matrices (strategy "direct") and the HOSVD init run as XLA dots
GRAMS = {"mnp,qnp->mq", "mnp,mqp->nq", "mnp,mnq->pq",
         "mjk,qjk->mq", "ink,iqk->nq", "ijp,ijq->pq"}


def read(run):
    t, c = run.trace, run.conf
    if t is None or not t["jobs"] or t["kernel_s"] <= 0 or run.peaks is None:
        return None
    bound = sum(run.peaks.bound_s(x.flops, x.nbytes) for x in
                counts.hooi_contractions(c["n"], c["ranks"], c["n_iter"])
                if x.spec not in GRAMS)
    return 100.0 * t["jobs"] * bound / t["kernel_s"]
