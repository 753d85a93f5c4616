"""Exact: the counts the timed iterations left (``C_wk``, ``C_dk``, the
totals ``C_k`` and every worker's synced and local view of them) equal a
recount of the assignments they left.  Covers the count fold, the ``C_k``
sync and the layout scatter; a mismatch in any cell is a fault."""
import numpy as np

from reference import lda as ref

LIMITS = {"count_mismatch": 0}


def check(o):
    z = np.asarray(o.z_after)
    bad = int(((z < 0) | (z >= o.num_topics)).sum())
    if bad:
        return [("count_mismatch", float(bad), LIMITS["count_mismatch"])]
    ckt, cdk, ck = ref.recount(o.word, o.doc, z, o.vocab_size, o.num_docs,
                               o.num_topics)
    mism = (int((np.asarray(o.ckt) != ckt).sum())
            + int((np.asarray(o.cdk) != cdk).sum())
            + int((np.asarray(o.ck) != ck).sum())
            + int((np.asarray(o.ck_synced) != ck).sum())
            + int((np.asarray(o.ck_local) != ck[None, :]).sum()))
    return [("count_mismatch", float(mism), LIMITS["count_mismatch"])]
