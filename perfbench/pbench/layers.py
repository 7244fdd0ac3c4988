"""What the per-layer metric readers share.

A reader is a file ``metrics/<name>.py`` with ``read(run) -> float | None``.
``run`` is a ``RunData``: the host's dispatch records, the reduced trace and
the work counts.  A reader that finds nothing to read returns None, and the
metric is left out of the result line; a share of a roofline or of a peak is
never reported as 0 for want of data.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

from pbench import work
from pbench.trace import kernel_times, program_times


@dataclass
class RunData:
    cell: object
    dims: work.Dims
    peak: Dict                 # the device's row of peaks.json
    chips: int
    calls: List                # serving.Call records of the whole run
    window: Optional[tuple]    # traced window (perf_counter seconds)
    reduced: Optional[Dict]    # trace.reduce(...) of that window

    def traced(self, kind: Optional[str] = None) -> List:
        if self.window is None:
            return []
        lo, hi = self.window
        return [c for c in self.calls if lo <= c.t0 and c.t1 <= hi
                and (kind is None or c.kind == kind)]

    def device_times(self) -> Dict[str, List[float]]:
        """Seconds of each program execution in the traced window, by the
        kind of dispatch it ran for."""
        if self.reduced is None:
            return {}
        return program_times(self.reduced, [c.kind for c in self.traced()])

    def kernel_times(self) -> Dict[str, List[float]]:
        return kernel_times(self.reduced)

    def work(self, call) -> work.Work:
        if call.kind == "decode":
            return work.decode_work(self.dims, call.info)
        off, length, final = call.info
        return work.chunk_work(self.dims, off, length, final)


def host_ms(run: RunData, kind: str) -> Optional[float]:
    """Mean host-clock milliseconds per dispatch of ``kind`` in the traced
    window, readback included."""
    calls = run.traced(kind)
    if not calls:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / len(calls)


def device_ms(run: RunData, kind: str) -> Optional[float]:
    """Mean device milliseconds per program execution of ``kind``."""
    times = run.device_times().get(kind)
    if not times:
        return None
    return 1e3 * sum(times) / len(times)


def roofline_pct(run: RunData, kind: str, bound: str = "max"
                 ) -> Optional[float]:
    """Least time over device time, summed over the executions of
    ``kind``: ``bound="max"`` takes the larger of the FLOP and byte roofs,
    ``"bytes"`` the HBM roof alone."""
    times = run.device_times().get(kind)
    if not times:
        return None
    calls = run.traced(kind)
    pf, pb = run.peak["bf16_flops_per_s"], run.peak["hbm_bytes_per_s"]
    least = 0.0
    for c in calls:
        w = run.work(c)
        least += w.bytes / pb if bound == "bytes" else w.least_s(pf, pb)
    return 100.0 * least / sum(times)


def mfu_pct(run: RunData) -> Optional[float]:
    """Model FLOPs of every dispatch in the traced window over the window
    times the chips' peak."""
    calls = run.traced()
    if not calls or run.reduced is None:
        return None
    flops = sum(run.work(c).flops for c in calls)
    return 100.0 * flops / (run.reduced["window_s"] * run.chips
                            * run.peak["bf16_flops_per_s"])


def idle_pct(run: RunData) -> Optional[float]:
    if run.reduced is None:
        return None
    return 100.0 * (1.0 - run.reduced["busy_s"] / run.reduced["window_s"])


@dataclass
class TrainData:
    """What a training cell's readers read: the steps of the whole run on
    the host clock, the traced window and its reduced trace."""
    cell: object
    dims: work.Dims
    peak: Dict
    chips: int
    steps: List                # training.Step records of the window
    window: Optional[tuple]
    reduced: Optional[Dict]

    @property
    def batch(self) -> int:
        return int(self.cell.traffic["global_batch"])

    @property
    def seq(self) -> int:
        return int(self.cell.traffic["seq_len"])

    def traced(self) -> List:
        if self.window is None:
            return []
        lo, hi = self.window
        return [s for s in self.steps if lo <= s.t0 and s.t1 <= hi]

    def kernel_times(self) -> Dict[str, List[float]]:
        return kernel_times(self.reduced)


def train_mfu_pct(run: TrainData) -> Optional[float]:
    """Model FLOPs (forward and backward, no recompute) of the steps in the
    traced window over the window times the chips' bf16 peak."""
    steps = run.traced()
    if not steps or run.reduced is None:
        return None
    flops = len(steps) * work.train_step_flops(run.dims, run.batch, run.seq)
    return 100.0 * flops / (run.reduced["window_s"] * run.chips
                            * run.peak["bf16_flops_per_s"])


def kernel_roofline_pct(run: TrainData, kernel: str) -> Optional[float]:
    """The least time of the ``kernel`` calls that the traced steps make,
    over the kernel's device time on the first chip.  Nothing to read when
    the trace holds another number of calls than the steps make."""
    steps = run.traced()
    times = run.kernel_times().get(kernel)
    calls = work.seam_calls(run.dims, run.batch, run.seq, run.chips)[kernel]
    if not steps or not times or len(times) != len(steps) * len(calls):
        return None
    pf, pb = run.peak["bf16_flops_per_s"], run.peak["hbm_bytes_per_s"]
    least = len(steps) * sum(w.least_s(pf, pb) for w in calls)
    return 100.0 * least / sum(times)
