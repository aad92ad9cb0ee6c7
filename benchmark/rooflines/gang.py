"""Operations and bytes of the batched gang program
(`nomad_tpu/ops/gang.py` `batched_gang_placement_program`) for one
dispatch of B lanes of K member steps over N node rows and G topology
groups, counted from the program's text. No metric reads them yet: the
benchmark has no table of the device's peaks and traces with
`enable_hlo_proto` off, so no share of a roofline can be formed
(`PERF.md` section 7); they are here for the `benchmark` issue that
brings one. Imports nothing.

A lane is: member units (per node, 4 resource dimensions, bandwidth,
ports), the scatter of units into group capacities, the slice choice
over G groups (twice in slice mode: once on the carried state, once on
the unclaimed base for `moved`), then K steps, each a pass over the N
rows (fit test and score in 4 dimensions, two powers, the noise, an
argmax) and four scatters of one row; then the lane's claims, three
scatters of K rows.
"""

F32 = 4
# One node row's arithmetic in one member step: 4 adds and 4 compares
# (fit), 2 compares (bandwidth, ports), 6 mask ANDs, 2 divides, 2
# subtracts and 2 powers (fitness; a power counted as 2: exp and
# multiply), clip (2), penalty (2), noise add, select, and the argmax's
# compare.
STEP_OPS_PER_ROW = 4 + 4 + 2 + 6 + 2 + 2 + 4 + 2 + 2 + 1 + 1 + 1
# Member units: 4 subtracts, 4 divides, 4 floors, 3 mins, the bandwidth
# and port terms (2 each: divide, floor; 2 mins), 3 selects.
UNITS_OPS_PER_ROW = 4 + 4 + 4 + 3 + 4 + 2 + 3


def operations(n: int, b: int, k: int, g: int, slice_mode: bool = True) -> int:
    """Arithmetic operations of one dispatch."""
    selects = 2 if slice_mode else 1
    lane = (selects * (UNITS_OPS_PER_ROW * n + n + 3 * g)   # units, scatter, choice
            + k * (STEP_OPS_PER_ROW * n + 4 * 4)            # the member scan
            + k * n                                         # the (K, N) noise draw
            + 3 * 4 * k)                                    # the lane's claims
    return b * lane


def bytes_moved(n: int, b: int, k: int, g: int, slice_mode: bool = True) -> int:
    """Bytes read and written in device memory by one dispatch, counting
    each member step's pass over the carried node state (the scan keeps
    it in HBM between steps) and every array once where it is only read
    once a lane."""
    state = (4 + 4 + 4 + 1 + 1 + 1) * n * F32       # capacity, sched, util, bw x2, ports
    per_lane_inputs = n * (1 + F32 + F32) + 6 * F32 + k      # feasibility, counts, ask, active
    step = (state + n * (1 + F32 + F32)     # the state, the mask, the counts
            + n * F32                       # the step's noise row
            + 4 * 4 * F32)                  # the four scatters
    selects = 2 if slice_mode else 1
    lane = (per_lane_inputs + selects * (state + n * F32 + g * F32)
            + k * step + k * n * F32        # the noise written once
            + 3 * k * F32 + 2 * k * F32)    # claims, results
    return b * lane
