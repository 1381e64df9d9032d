from repro.kernels.seg_aggr.ops import seg_aggr
from repro.kernels.seg_aggr.ref import seg_aggr_ref

__all__ = ["seg_aggr", "seg_aggr_ref"]
