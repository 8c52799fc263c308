"""Bytes the state pull brought to the host over the bytes of the states
themselves, from the counts the program's spans carry: every ``bytes`` of
``replay.fetch.wait`` (one fetch, or a guess and its refetch) over ``aggregates``
of ``replay.fetch`` times the configuration's ``work.state_row_bytes``. Under 1
where columns ride a half-width wire, over 1 where a rebuild fetches twice."""

from benchmarks import spans


def read(run):
    found = spans.program_spans(run)
    if found is None:
        return None
    fetched = [r["attributes"]["bytes"] for r in found[0]
               if r["name"] == "replay.fetch.wait"
               and "bytes" in r["attributes"]]
    pulled = sum(r["attributes"].get("aggregates", 0) for r in found[0]
                 if r["name"] == "replay.fetch")
    if not fetched or not pulled:
        return None
    return sum(fetched) / (pulled * run.config["work"]["state_row_bytes"])
