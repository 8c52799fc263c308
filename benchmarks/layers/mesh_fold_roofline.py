"""The sharded cold fold's share of its roofline, from the device trace.

``fold_roofline`` over a mesh: the same least bytes (the corpus's, unpadded,
whatever implements the fold) over the summed HBM bandwidth of the chips the
trace shows, against the device time of the layer's programs, which the trace
reduction gives as the average over those chips. A log folded at exactly the
chips' bandwidth, each chip busy with its share, reads 100.
"""

from benchmarks.layers import fold_roofline


def read(run):
    one_chip = fold_roofline.read(run)  # the bytes over ONE chip's bandwidth
    if one_chip is None:
        return None
    return one_chip / run.traced["chips"]
