"""Bytes put on the device over the bytes of the packed wire, from the counts
``replay.h2d`` carries: what bucketing the buffers to a power of two costs the
upload (counted by the program)."""

from benchmarks import spans


def read(run):
    found = spans.program_spans(run)
    if found is None:
        return None
    uploads = [r["attributes"] for r in found[0] if r["name"] == "replay.h2d"
               and r["attributes"].get("wire_bytes")]
    if not uploads:
        return None
    return (sum(a["put_bytes"] for a in uploads)
            / sum(a["wire_bytes"] for a in uploads))
