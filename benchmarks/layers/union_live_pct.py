"""Share of the union's side-column bytes that some handler of the row's own
type reads, from the counts ``replay.mixed.merge`` carries (``live_side_bytes``
over ``union_side_bytes``): what the tagged-union layout carries through pack,
upload and fetch for the fold to use, against the zeros of the columns other
families own. The merge is set-up, so the span is looked for in the whole ring,
not in the window."""


def read(run):
    try:
        from surge_tpu.tracing import default_tracer
    except ImportError:
        return None
    merges = [s.attributes for s in default_tracer().spans()
              if s.name == "replay.mixed.merge"
              and s.attributes.get("union_side_bytes")]
    if not merges:
        return None
    return (100.0 * sum(a["live_side_bytes"] for a in merges)
            / sum(a["union_side_bytes"] for a in merges))
