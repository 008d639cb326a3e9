"""The verify tile's submit rule as the benchmark reads it (ISSUE 26):
the two counters the rule brings (`held_batches`, `full_batches`, beside
`device_batches`) and the flood's twins of three batch-lifecycle rows —
five metric files, data only: the share's reader on toy snapshots, and
each flood twin against its paced row.  (The CPU rehearsals of both cells in
test_verify_spans.py run with these files in place; a third rehearsal
here would only add a minute of fourteen spinning processes to a suite
whose timing tests already feel the two.)
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import run as RUN  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")

NEW = {
    "leader.paced": ["verify.held_batch_share.leader"],
    "ingress.flood": [
        "verify.held_batch_share.ingress", "verify.fill_ms_per_batch.ingress",
        "verify.queue_ms_per_batch.ingress",
        "verify.inflight_ms_per_batch.ingress"],
}


def _snaps(before, after):
    return {"before": {"verify0": before}, "after": {"verify0": after}}


@pytest.mark.parametrize("before,after,want", [
    ((10, 2, 12), (110, 2, 112), 100.0),   # every batch of the window held
    ((10, 2, 12), (60, 12, 112), 50.0),    # half held, a tenth full
    ((10, 2, 12), (10, 42, 112), 0.0),     # none held: a true 0
    ((10, 2, 12), (10, 2, 12), None),      # no batch in the window
])
def test_held_batch_share_is_held_over_device_batches(before, after, want):
    read = RUN.load_reader(ROOT, "counters_ratio")
    keys = ("held_batches", "full_batches", "device_batches")
    ctx = _snaps(dict(zip(keys, before)), dict(zip(keys, after)))
    for cell, short in (("leader.paced", "leader"), ("ingress.flood", "ingress")):
        m = RUN.load_metrics(ROOT, cell, end_to_end=False)[
            f"verify.held_batch_share.{short}"]
        assert read(ctx, **m["args"]) == want
    # the parent commit has no such counter: nothing, and no error
    old = _snaps({"device_batches": 12}, {"device_batches": 112})
    assert read(old, **m["args"]) is None


def test_a_flood_twin_reads_the_words_its_paced_row_reads():
    """The file-against-manifest check of every metric is
    test_benchmark.py's; what is this PR's own: the five are rows of the
    verify tile's layer, one cell each, and a flood twin is its paced
    row's reader and arguments, word for word."""
    paced = RUN.load_metrics(ROOT, "leader.paced", end_to_end=False)
    for cell, names in NEW.items():
        found = RUN.load_metrics(ROOT, cell, end_to_end=False)
        for name in names:
            f = found[name]
            assert f["workloads"] == [cell]
            assert f["layer"] == "verify tile (host)"
            twin = paced[name.replace(".ingress", ".leader")]
            assert (f["reader"], f["args"]) == (twin["reader"], twin["args"])
