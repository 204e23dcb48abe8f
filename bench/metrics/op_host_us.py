"""Host time per allocator op (set or delete through a tenant's
SlabAllocator, stored, refused or deleted), microseconds, over the
window's rounds."""


def read(run):
    cell = run.cell
    if run.config["kind"] != "fleet":
        return None
    ns = sum(b - a for a, b in run.spans.records.get("ops", []))
    ops = sum(cell.round_ops)
    return ns / 1e3 / ops if ops else None
