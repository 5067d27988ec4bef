"""knn_fused_topk's share (%) of its roofline, from the device trace."""
from bench import lib, roofline


def read(run):
    return lib.roofline_share(run, "knn_fused_topk", roofline.knn_fused_topk)
