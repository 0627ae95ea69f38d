"""Metric arithmetic: from the run's records to numbers.

Every end-to-end and per-layer number is computed here, from what the
run recorded: per request its due, submit and done times on the host's
clock; per plan the proxy's record; and, in a traced run, the reduced
device trace.  A per-layer reader under ``chipbench/metrics/`` calls one
function here and returns ``None`` when there is nothing to read.

Percentiles are numpy's linear interpolation over every request that
completed in the window.  A share of a peak or of a roofline is never
reported as 0 for want of data: it is ``None`` then.
"""

from __future__ import annotations

import numpy as np

from chipbench import proxy as P

#: Peak to read a compute dtype against: float32 runs on the MXU as
#: several bfloat16 passes, so bfloat16 is the fastest float mode and
#: the honest ceiling for both.
PEAK_FOR_DTYPE = {"float32": "bfloat16", "bfloat16": "bfloat16",
                  "int8": "int8"}


def percentile(values, q: float) -> float | None:
    values = np.asarray(values, np.float64)
    values = values[np.isfinite(values)]
    return float(np.percentile(values, q)) if values.size else None


def done_times(plans, n_requests: int) -> np.ndarray:
    """Per request: when the last plan carrying one of its jets had its
    logits on the host (``nan`` if that never happened)."""
    done = np.full(n_requests, -np.inf)
    for rec in plans:
        t = rec[P.T_REALIZED]
        if t is None:
            continue
        for rid in rec[P.RIDS]:
            if rid < n_requests and t > done[rid]:
                done[rid] = t
    done[np.isneginf(done)] = np.nan
    return done


def last_dispatch(plans, n_requests: int) -> np.ndarray:
    """Per request: when the plan carrying its last jet was dispatched."""
    out = np.full(n_requests, np.nan)
    for rec in plans:
        for rid in rec[P.RIDS]:
            if rid < n_requests:
                out[rid] = rec[P.T_DISPATCH]    # plans dispatch in order
    return out


# -- end to end ---------------------------------------------------------------

def window_end(run) -> float | None:
    ok = np.isfinite(run.done)
    return float(np.max(run.done[ok])) if ok.any() else None


def events_per_s(run) -> float | None:
    """Jets whose logits reached the host, over the whole window: from
    its opening to the last answer."""
    end = window_end(run)
    if end is None or end <= run.t0:
        return None
    ok = np.isfinite(run.done)
    return float(run.log.jets[ok].sum()) / (end - run.t0)


def latencies_s(run) -> np.ndarray:
    """Logits on the host minus the time the request was due."""
    return run.done - run.log.due


def latency_ms(run, q: float) -> float | None:
    v = percentile(latencies_s(run), q)
    return None if v is None else v * 1e3


# -- per layer: load generator, front end, engine --------------------------

def gen_lag_p99_ms(run) -> float | None:
    v = percentile(run.log.submit - run.log.due, 99)
    return None if v is None else v * 1e3


def queue_wait_p99_ms(run) -> float | None:
    wait = last_dispatch(run.plans, len(run.log.due)) - run.log.due
    v = percentile(wait, 99)
    return None if v is None else v * 1e3


def pad_share(run) -> float | None:
    rows = sum(rec[P.BUCKET] for rec in run.plans)
    valid = sum(rec[P.N_VALID] for rec in run.plans)
    return 100.0 * (rows - valid) / rows if rows else None


def loop_blocked_share(run) -> float | None:
    """Share of the window, in %, that the open-loop generator spent
    held inside single calls into the server longer than
    ``loadgen.STALL_S``."""
    end = window_end(run)
    if end is None or end <= run.t0:
        return None
    return 100.0 * sum(d for _, d in run.log.stalls) / (end - run.t0)


def dispatch_us_per_plan(run) -> float | None:
    spans = [rec[P.T_DISPATCHED] - rec[P.T_DISPATCH] for rec in run.plans]
    return 1e6 * float(np.mean(spans)) if spans else None


# -- per layer: device ----------------------------------------------------------

def device_idle_share(run) -> float | None:
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)


def peak_flops(run) -> float:
    return float(run.peak["flops_per_s"][PEAK_FOR_DTYPE[run.cfg[
        "compute_dtype"]]])


def kernel_roofline(run, kernel: str) -> tuple[float, str] | None:
    """Share of the roofline of the kernel whose HLO instruction name
    starts with ``kernel``: the least time the chip could take for
    every plan's call (the larger of FLOPs over peak and bytes over
    HBM bandwidth, at the plan's bucket, each chip its share of the
    rows) over the kernel's device time.  Returns (share %, bound)."""
    tr = run.trace
    if tr is None:
        return None

    def match(name: str) -> bool:
        return name.startswith("%" + kernel) and "custom-call" in name

    t_kernel = tr.op_seconds(match)
    if t_kernel <= 0 or not run.plans:
        return None
    chips, peak = run.chips, peak_flops(run)
    bw = float(run.peak["hbm_bytes_per_s"])
    t_flops = t_bytes = t_min = 0.0
    for rec in run.plans:
        rows = rec[P.BUCKET] / chips
        f = chips * run.work.flops_per_jet(run.cfg) * rows / peak
        b = chips * run.work.call_bytes(run.cfg, rows) / bw
        t_flops, t_bytes, t_min = t_flops + f, t_bytes + b, t_min + max(f, b)
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * t_min / t_kernel, bound


def step_mfu(run) -> float | None:
    """Model FLOPs of the valid jets served, over the step programs'
    device time (summed over chips) at the chip's peak."""
    tr = run.trace
    if tr is None:
        return None
    t_steps = tr.module_seconds()
    valid = sum(rec[P.N_VALID] for rec in run.plans
                if rec[P.T_REALIZED] is not None)
    if t_steps <= 0 or not valid:
        return None
    flops = valid * run.work.flops_per_jet(run.cfg)
    return 100.0 * flops / (t_steps * peak_flops(run))
