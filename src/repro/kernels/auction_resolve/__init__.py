from repro.kernels.auction_resolve.ops import (auction_resolve, round_fused,
                                               sweep_partials, sweep_resolve)
from repro.kernels.auction_resolve.ref import (auction_resolve_ref,
                                               fused_partials_ref,
                                               resolve_tile_ref,
                                               round_fused_ref,
                                               sweep_resolve_ref, valuations)

__all__ = ["auction_resolve", "auction_resolve_ref",
           "fused_partials_ref", "resolve_tile_ref", "round_fused",
           "round_fused_ref", "sweep_partials", "sweep_resolve",
           "sweep_resolve_ref", "valuations"]
