"""Plain reference of the multi-device cascade: a discrete-event loop in
float64 over a priority heap, with the three schedulers written out in
numpy. It imports nothing of the program.

It follows the paper's system (MultiTASC++, arXiv 2412.04147, Fig. 2)
and the event order the program documents in ``sim/events.py``: at one
instant joins, then leaves, then device completions, then a server batch
finish and launch (launch after the whole same-instant completion cluster
has enqueued), then the SLO window boundary. Devices run their stream
back to back (sample j starts at max(previous finish, arrive[j])),
forward a sample when its confidence is below the device's threshold
(Eq. 3), the server drains its FIFO in batches of the largest ladder
size that the queue and the model's cap admit, and each window every
active device reports its window SLO rate to the scheduler.

``attribute`` says when a forwarded sample's completion is counted
(SLO met or not, correct or not, in the device's window and totals):
``"finish"``, when its batch finishes, as a deployment sees it, or
``"launch"``, when its batch is launched (its finish time is known
then), as the program documents of its own window accounting
(``sim/jaxsim.py``: "window SR attribution happens at batch launch").
The two differ only for batches that straddle a window boundary.

``rnd`` rounds every real number the loop stores: the identity for the
reference, and a cast through bfloat16 for the lower-precision control
(``perfbench/controls.py``).
"""
from __future__ import annotations

import dataclasses
import heapq
from collections import deque
from typing import Callable, Dict, List

import numpy as np

EV_JOIN, EV_LEAVE, EV_DEV, EV_SRV, EV_WINDOW = 0, 1, 2, 4, 5


def identity(x: float) -> float:
    return x


def rounder(dtype) -> Callable[[float], float]:
    """Round a float through ``dtype`` (e.g. ``ml_dtypes.bfloat16``)."""
    def rnd(x: float) -> float:
        return float(np.asarray(x, dtype))
    return rnd


@dataclasses.dataclass(frozen=True)
class Server:
    base_latency: float
    max_batch: int
    batch_scaling: float
    ladder: tuple

    def batch_latency(self, b: int) -> float:
        return self.base_latency * (1.0 + self.batch_scaling * (b - 1))


# ---------------------------------------------------------------------------
# schedulers (paper Sec. IV; MultiTASC of ref [11]; the static baseline)
# ---------------------------------------------------------------------------
class MultiTASCPP:
    """Eq. 4, dthresh = -a (target - sr), per device on its own report,
    with Alg. 1's multiplier: while a device's threshold is raised it is
    scaled by m, and m grows by (1 + growth / n_active); else m = 1."""

    def __init__(self, n, *, a, sr_target, init_threshold, growth=0.1,
                 rnd=identity):
        self.a, self.target, self.growth, self.rnd = a, sr_target, growth, rnd
        self.thresh = [rnd(init_threshold)] * n
        self.mult = [1.0] * n
        self.n_active = n

    def set_active(self, active):
        self.n_active = max(int(np.sum(active)), 1)

    def report(self, i, sr):
        rnd = self.rnd
        upd = rnd(self.thresh[i] + rnd(-self.a * (self.target - sr)))
        if sr > self.target:
            upd = rnd(self.mult[i] * upd)
            self.mult[i] = rnd(self.mult[i] * (1.0 + self.growth
                                               / self.n_active))
        else:
            self.mult[i] = 1.0
        self.thresh[i] = min(max(upd, 0.0), 1.0)
        return self.thresh[i]

    def on_server_batch(self, b):
        pass

    def on_window(self, active):
        return None


class MultiTASC:
    """Fixed-step update of every active device from the running server
    batch size against b*, the largest ladder batch whose latency is at
    most 0.3 of the SLO."""

    def __init__(self, n, *, server: Server, slo, init_threshold,
                 step=0.05, rnd=identity):
        self.thresh = [rnd(init_threshold)] * n
        self.step, self.rnd = step, rnd
        self.b_opt = 1
        for b in server.ladder:
            if b <= server.max_batch and server.batch_latency(b) <= 0.3 * slo:
                self.b_opt = b
        self.recent = 0

    def set_active(self, active):
        pass

    def report(self, i, sr):
        return self.thresh[i]

    def on_server_batch(self, b):
        self.recent = b

    def on_window(self, active):
        d = (-self.step if self.recent > self.b_opt
             else self.step if self.recent < self.b_opt else 0.0)
        self.thresh = [min(max(self.rnd(t + d), 0.0), 1.0) if a else t
                       for t, a in zip(self.thresh, active)]
        return self.thresh


class Static:
    def __init__(self, n, *, threshold, rnd=identity):
        self.thresh = [rnd(threshold)] * n

    def set_active(self, active):
        pass

    def report(self, i, sr):
        return self.thresh[i]

    def on_server_batch(self, b):
        pass

    def on_window(self, active):
        return None


def make_scheduler(name: str, n: int, *, server: Server, slo: float,
                   a: float, sr_target: float, init_threshold: float,
                   static_threshold: float, rnd=identity):
    if name == "multitasc++":
        return MultiTASCPP(n, a=a, sr_target=sr_target,
                           init_threshold=init_threshold, rnd=rnd)
    if name == "multitasc":
        return MultiTASC(n, server=server, slo=slo,
                         init_threshold=init_threshold, rnd=rnd)
    if name == "static":
        return Static(n, threshold=static_threshold, rnd=rnd)
    raise KeyError(name)


# ---------------------------------------------------------------------------
# the event loop
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Result:
    sr: float
    accuracy: float
    throughput: float
    forwarded_frac: float
    completed: int
    queue_left: int
    per_device_sr: np.ndarray
    per_device_acc: np.ndarray
    # per window: mean window SR, mean running accuracy, cumulative
    # forwards, mean threshold of the active devices
    trace_sr: np.ndarray
    trace_acc: np.ndarray
    trace_fwd: np.ndarray
    trace_thresh: np.ndarray


def run(confidence, correct_light, correct_heavy, latency, slo,
        server: Server, scheduler, *, window: float,
        arrive=None, join_t=None, leave_t=None, rnd=identity,
        attribute: str = "finish", max_time: float = 1e5) -> Result:
    """Simulate one fleet. ``confidence``/``correct_*``/``arrive`` are
    (N, S) per-device streams, ``latency``/``slo``/``join_t``/``leave_t``
    (N,) per-device values, in seconds."""
    conf = [[rnd(float(c)) for c in row] for row in np.asarray(confidence)]
    cl = np.asarray(correct_light).astype(int).tolist()
    ch = np.asarray(correct_heavy).astype(int).tolist()
    n, s = len(conf), len(conf[0])
    lat = [rnd(float(x)) for x in np.broadcast_to(latency, (n,))]
    slo = [rnd(float(x)) for x in np.broadcast_to(slo, (n,))]
    join = np.zeros(n) if join_t is None else np.asarray(join_t, float)
    leave = np.full(n, np.inf) if leave_t is None else np.asarray(
        leave_t, float)
    arr = None if arrive is None else np.asarray(arrive, float).tolist()

    if attribute not in ("finish", "launch"):
        raise ValueError(f"attribute must be finish or launch: {attribute}")
    at_launch = attribute == "launch"

    def arrival(i, j):
        return 0.0 if arr is None else rnd(arr[i][j])

    heap: list = []
    seq = [0]

    def push(t, kind, payload=None):
        heapq.heappush(heap, (rnd(t), kind, seq[0], payload))
        seq[0] += 1

    thresh = list(scheduler.thresh)
    cursor = [0] * n
    met = [0] * n
    total = [0] * n
    correct = [0] * n
    fwd = [0] * n
    win_met = [0] * n
    win_total = [0] * n
    win_sr_last = [100.0] * n
    joined = [True] * n
    departed = [False] * n
    queue: deque = deque()
    busy = [False]
    last_done = [0.0]
    tr: Dict[str, List[float]] = {"sr": [], "acc": [], "fwd": [],
                                  "thresh": []}

    def complete(i, latency_i, ok, t):
        m = latency_i <= slo[i]
        met[i] += m
        win_met[i] += m
        total[i] += 1
        win_total[i] += 1
        correct[i] += ok
        last_done[0] = max(last_done[0], t)

    def try_launch(t):
        if busy[0] or not queue:
            return
        b = 1
        for x in server.ladder:
            if x <= min(len(queue), server.max_batch):
                b = x
        batch = [queue.popleft() for _ in range(b)]
        scheduler.on_server_batch(b)
        busy[0] = True
        finish = rnd(t + rnd(server.batch_latency(b)))
        if at_launch:
            for start, i, j in batch:
                complete(i, rnd(finish - start), ch[i][j], finish)
            batch = []
        push(finish, EV_SRV, batch)

    for i in range(n):
        if join[i] > 0.0:
            joined[i] = False
            push(join[i], EV_JOIN, i)
        else:
            push(max(join[i], arrival(i, 0)) + lat[i], EV_DEV, i)
        if np.isfinite(leave[i]):
            push(leave[i], EV_LEAVE, i)
    push(window, EV_WINDOW)

    while heap:
        t, kind, _, payload = heapq.heappop(heap)
        if t > max_time:
            break
        if kind == EV_JOIN:
            joined[payload] = True
            if cursor[payload] < s:
                push(max(t, arrival(payload, cursor[payload]))
                     + lat[payload], EV_DEV, payload)
        elif kind == EV_LEAVE:
            departed[payload] = True
        elif kind == EV_DEV:
            i = payload
            if cursor[i] < s:
                if departed[i]:
                    cursor[i] = s
                else:
                    j = cursor[i]
                    cursor[i] += 1
                    if conf[i][j] >= thresh[i]:
                        complete(i, lat[i], cl[i][j], t)
                    else:
                        fwd[i] += 1
                        queue.append((rnd(t - lat[i]), i, j))
                    if cursor[i] < s:
                        push(max(t, arrival(i, cursor[i])) + lat[i],
                             EV_DEV, i)
            if not heap or heap[0][0] != t or heap[0][1] != EV_DEV:
                try_launch(t)
        elif kind == EV_SRV:
            busy[0] = False
            for start, i, j in payload:
                complete(i, rnd(t - start), ch[i][j], t)
            try_launch(t)
        elif kind == EV_WINDOW:
            active = [joined[i] and not departed[i] for i in range(n)]
            scheduler.set_active(active)
            for i in range(n):
                if not active[i]:
                    continue
                sr = 100.0 if win_total[i] == 0 else rnd(
                    100.0 * win_met[i] / win_total[i])
                win_sr_last[i] = sr
                win_met[i] = win_total[i] = 0
                thresh[i] = scheduler.report(i, sr)
            new = scheduler.on_window(active)
            if new is not None:
                thresh = list(new)
            act = [thresh[i] for i in range(n) if active[i]]
            tr["sr"].append(float(np.mean(win_sr_last)))
            tr["acc"].append(float(np.mean(
                [correct[i] / total[i] if total[i] else 1.0
                 for i in range(n)])))
            tr["fwd"].append(float(sum(fwd)))
            tr["thresh"].append(float(np.mean(act)) if act else np.nan)
            if any(c < s for c in cursor) or queue or busy[0]:
                push(t + window, EV_WINDOW)

    tot = np.asarray(total, float)
    per_sr = np.where(tot > 0, 100.0 * np.asarray(met) / np.maximum(tot, 1),
                      100.0)
    per_acc = np.where(tot > 0, np.asarray(correct) / np.maximum(tot, 1),
                       1.0)
    n_done = int(tot.sum())
    return Result(
        sr=float(100.0 * sum(met) / max(n_done, 1)),
        accuracy=float(per_acc.mean()),
        throughput=float(n_done / max(last_done[0], 1e-9)),
        forwarded_frac=float(sum(fwd) / max(n_done, 1)),
        completed=n_done, queue_left=len(queue),
        per_device_sr=per_sr, per_device_acc=per_acc,
        trace_sr=np.asarray(tr["sr"]), trace_acc=np.asarray(tr["acc"]),
        trace_fwd=np.asarray(tr["fwd"]),
        trace_thresh=np.asarray(tr["thresh"]))
