#!/usr/bin/env python3
"""The control of `correct`: the program with its capacity sums held in
bfloat16, the step a later PR could be tempted by. It has to come out as
not correct.

    python benchmark/control.py --workload <name> --seed <n> --seconds <s>

runs one cell exactly as `run.py` does, on the chip at the cell's own
size, with the patch below applied from outside (no option of the
program is involved). Every line is marked `CONTROL`, so the last line is
never a result. `tests/test_control.py` runs the same patch in a
rehearsal.
"""

from __future__ import annotations

import contextlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


@contextlib.contextmanager
def sums_in_bfloat16():
    """While active, what the device holds of the cluster base's
    utilisation and bandwidth-in-use columns (the running sums of
    allocations) is rounded through bfloat16, on upload and after every
    delta. MHz and MB in the hundreds and thousands need more than
    bfloat16's 8 bits, so the device plans against sums that are not the
    store's."""
    import jax.numpy as jnp
    from nomad_tpu.ops import binpack

    def through_bf16(x):
        return x.astype(jnp.bfloat16).astype(x.dtype)

    resident, delta = binpack.device_resident, binpack.apply_base_delta

    def device_resident(*arrays):
        dev = list(resident(*arrays))
        dev[2], dev[4] = through_bf16(dev[2]), through_bf16(dev[4])
        return tuple(dev)

    def apply_base_delta(*args):
        util, bw, ports, ok = delta(*args)
        return through_bf16(util), through_bf16(bw), ports, ok

    # `jit_cache_size()` asks every entry point for its program count.
    device_resident._cache_size = resident._cache_size
    apply_base_delta._cache_size = delta._cache_size
    binpack.device_resident = device_resident
    binpack.apply_base_delta = apply_base_delta
    try:
        yield
    finally:
        binpack.device_resident = resident
        binpack.apply_base_delta = delta


def main(argv=None) -> int:
    import run

    with sums_in_bfloat16():
        return run.main(argv, mark="CONTROL bf16 ")


if __name__ == "__main__":
    sys.exit(main())
