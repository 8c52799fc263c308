"""The upload's achieved rate, in 1e9 bytes a second: ``put_bytes`` of the
``replay.h2d.put`` spans over their seconds (every ``device_put`` and
placement through ``block_until_ready``; on a mesh the devices' uploads side
by side)."""

from benchmarks import stage_usage


def read(run):
    return stage_usage.ratio(run, ("replay.h2d.put",),
                             stage_usage.attribute("put_bytes"),
                             stage_usage.seconds, scale=1e-9)
