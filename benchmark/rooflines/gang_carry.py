"""Operations and bytes of the batched gang program as PR 42 left it
(`nomad_tpu/ops/gang.py` `batched_gang_placement_program`): what
`gang.py` beside this file counts for one dispatch of B lanes of K
member steps over N node rows and G topology groups, plus the scan's
final carry, which the program now returns (utilisation [N, 4],
bandwidth [N] and free ports [N] after every lane's claims) so that
later dispatches on the same base token start from it on the device. The
arithmetic is unchanged: the carry was computed before and dropped. As
with `gang.py`, no metric reads these yet (`PERF.md` section 7); they
are here for the `benchmark` issue that brings a table of peaks.
Imports nothing of the program."""

import importlib.util
import os

F32 = 4


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"rooflines_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_gang = _sibling("gang")


def operations(n: int, b: int, k: int, g: int, slice_mode: bool = False) -> int:
    """Arithmetic operations of one dispatch: `gang.py`'s."""
    return _gang.operations(n, b, k, g, slice_mode)


def carry_bytes(n: int) -> int:
    """The carry written out once a dispatch: util, bw_used, ports_free."""
    return (4 + 1 + 1) * n * F32


def bytes_moved(n: int, b: int, k: int, g: int, slice_mode: bool = False) -> int:
    """Bytes read and written in device memory by one dispatch."""
    return _gang.bytes_moved(n, b, k, g, slice_mode) + carry_bytes(n)
