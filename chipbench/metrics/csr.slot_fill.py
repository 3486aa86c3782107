"""Share of the CSR slots that hold a nonzero: ``sparse.csr.nnz`` over
``sparse.csr.slots``, the counters ``CSRMatrix.to_rows`` sets when set-up
packs the rows (so the registry's totals, not the window's movement)."""
from chipbench import harness


def read(run):
    counters = harness.counters()
    slots = counters.get("sparse.csr.slots")
    if not slots:
        return None
    return 100.0 * counters.get("sparse.csr.nnz", 0) / slots
