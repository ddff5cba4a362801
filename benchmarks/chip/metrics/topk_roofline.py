"""topk_roofline (kernels layer): the top-k scoring kernel's share (%) of
its HBM roofline.

For each call of the AND-popcount scoring kernel (``kernels/topk_ops.py``
``row_and_card``, which appears in a v5e trace as the ``custom-call``
``%similarity_topk.N = ... custom-call(...)``) in the device trace, the
least time is (8192 bytes x the candidate rows of the engine's slab + the
query block's bytes) / the HBM peak of the device kind (``peaks.json``).
The metric is the sum of those least times over the summed device time
of the same calls.

The count assumes that a call streams the whole candidate slab once,
however many queries that one call scores: a later change that batches
queries into one pass keeps a true count.  A change that prunes
candidates on the device changes the work, and needs a benchmark change
to recount first."""

import json
import re
from pathlib import Path

PATTERN = re.compile(r"^%similarity_topk(\.\d+)? = .*custom-call\(")
ROW_BYTES = 8192


def read(run):
    if run.reduced is None:
        return None
    sec, calls = run.reduced.op_time(PATTERN.match)
    if not calls or sec <= 0:
        return None
    peaks = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())
    peak = peaks[run.device_kind]["hbm_bytes_per_s"]
    _, eng = run.index._sim_engine()
    rows = int(eng.rows.shape[0])
    cols = int(eng.row_col.max()) + 1 if rows else 1
    least = calls * (ROW_BYTES * rows + ROW_BYTES * cols) / peak
    return least / sec * 100.0
