from repro.kernels.lane_superstep.ops import (  # noqa: F401
    LaneCSR,
    fused_lane_superstep,
    gather_chunks,
    lane_csr_from_device_graph,
)
