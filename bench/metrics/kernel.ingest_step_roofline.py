"""The ingest step's share of its roofline, in %: the least time the
chip needs to read every published row's 4-byte codes once from HBM (or
to do its operations, whichever bounds), counted from the row count,
width and tree depth alone (``qdbench.work.ingest_step``), over the
device's busy time in the traced window.  The same work is counted
whichever backend implements the step."""

from qdbench import work


def read(layer, peaks):
    t = layer.get("trace")
    if layer.get("kind") != "ingest" or t is None or not layer["rows"] \
            or t.busy_s <= 0:
        return None
    least, _ = work.roofline_s(
        work.ingest_step(layer["rows"], layer["cols"], layer["depth"]), peaks)
    return 100.0 * least / t.busy_s
