"""Plain reference of the CIM fabric model, written from the paper and the
configuration files, importing nothing of the program under test.

It decides ``correct`` for every cell whose configuration names no other
reference module (``"reference": "<module>"``, for a file
``benchmarks/chip/<module>.py`` whose name starts with ``reference``;
``traffic.reference_of`` loads it).  Its only input from a run is the
simulator's data: the quantized activation samples of the set-up capture
(``sampled_q`` per layer), which are what a profile is computed from.  From
those it derives every cycle count, statistic, allocation, throughput and
replayed completion time itself.  The first layer's samples it also makes
itself (``first_layer_samples``), from the calibration image the
configuration names, so that a fault in the capture's image, patches,
quantization or sampling shows.

Model (arXiv:2008.06741, Sections II-V):

* A conv layer is a (k*k*cin) x cout matrix tiled over crossbars.  A block
  is one tile-row: ``ceil(rows / array_rows)`` blocks of
  ``ceil(cout / (cols * cell_bits / weight_bits))`` arrays each.
* Inputs enter bit-serially, ``input_bits`` planes.  With zero-skipping a
  plane costs ``max(1, ceil(ones / 2**adc_bits))`` reads; without it,
  ``ceil(rows / 2**adc_bits)``.  A read costs ``adc_share`` cycles.
* Policies: ``baseline`` (no zero-skip, replicas by MACs), ``weight_based``
  (zero-skip, replicas by MACs), ``perf_layerwise`` (zero-skip, greedy
  replicas by expected layer latency), ``blockwise`` (zero-skip, greedy
  replicas per block, blocks dispatch independently).  Proportional shares
  are floored and the rest goes by largest remainder; the greedy loop grants
  the slowest unit (ties: lowest index) until it cannot be afforded.
* Throughput: layer-wise, a patch waits for its slowest block; block-wise,
  every block is its own replicated pool.  The pipeline runs at its slowest
  layer.
* Replay: requests in index order through every layer; each (patch, block)
  job runs FIFO on the earliest-free replica of its pool, all jobs of a
  layer ready when the request enters it.

``dtype`` selects the arithmetic: float64 is the configuration's precision,
float32 is the control that the comparison has to reject.

What a reference module gives, and imports nothing of the program for:

* the capture check, every kind: ``first_layer_samples(config, f)``, the
  first crossbar layer's sampled quantized rows, ``f`` its arithmetic;
* the ``sweep`` kind: ``Array(**config["array"])`` with
  ``variant(rows, adc_bits)``; ``min_pes(config, array)`` and
  ``n_blocks(config, array)``; ``Network(config, sampled_q, array)``;
  ``allocate(net, policy, n_pes, f)``, a design; and ``evaluate(net,
  design, f)``, its ``total_cycles``, ``images_per_sec``,
  ``mean_utilization``, ``arrays_used`` and ``arrays_total``;
* the ``replay`` kind: the ``sweep`` kind's, a ``Network`` with ``min_pes``,
  ``q`` and ``layers`` (each with ``patches``, its jobs a request), and
  ``service_indices(seed, samples, patches, n)`` and ``replay(net, designs,
  arrivals, idx, f)``, the completion times.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass

import numpy as np

POLICIES = ("baseline", "weight_based", "perf_layerwise", "blockwise")
LAYERWISE = ("baseline", "weight_based", "perf_layerwise")


@dataclass(frozen=True)
class Layer:
    name: str
    kernel: int
    cin: int
    cout: int
    out_hw: int
    stride: int

    @property
    def rows(self) -> int:
        return self.kernel * self.kernel * self.cin

    @property
    def patches(self) -> int:
        return self.out_hw * self.out_hw

    @property
    def macs(self) -> int:
        return self.patches * self.rows * self.cout


def layers_of(config: dict) -> list[Layer]:
    cols = config["layer_columns"]
    return [Layer(**dict(zip(cols, row))) for row in config["layers"]]


@dataclass(frozen=True)
class Array:
    rows: int
    cols: int
    cell_bits: int
    weight_bits: int
    input_bits: int
    adc_bits: int
    adc_share: int

    @property
    def weights_per_array(self) -> int:
        return self.cols * self.cell_bits // self.weight_bits

    def variant(self, rows: int, adc_bits: int) -> "Array":
        return Array(rows, rows, self.cell_bits, self.weight_bits,
                     self.input_bits, adc_bits, self.adc_share)


def blocks(layer: Layer, array: Array) -> tuple[list[tuple[int, int]], int]:
    """Row ranges of the layer's blocks and the arrays in each block."""
    bounds = [(r, min(r + array.rows, layer.rows)) for r in range(0, layer.rows, array.rows)]
    width = -(-layer.cout // array.weights_per_array)
    return bounds, width


def tiling(config: dict, array: Array) -> list[tuple[list[tuple[int, int]], int]]:
    return [blocks(layer, array) for layer in layers_of(config)]


def n_blocks(config: dict, array: Array) -> int:
    return sum(len(b) for b, _ in tiling(config, array))


def min_pes(config: dict, array: Array) -> int:
    arrays = sum(len(b) * w for b, w in tiling(config, array))
    return -(-arrays // int(config["arrays_per_pe"]))


class Network:
    """Per-(geometry, ADC) view of one configuration and its activation data."""

    def __init__(self, config: dict, sampled_q: list[np.ndarray], array: Array):
        self.layers = layers_of(config)
        self.array = array
        self.arrays_per_pe = int(config["arrays_per_pe"])
        self.clock_hz = float(config["clock_hz"])
        self.eval_images = int(config["eval_images"])
        self.q = sampled_q
        self.bounds, self.width, self.zskip, self.base = [], [], [], []
        for layer, q in zip(self.layers, sampled_q):
            b, w = blocks(layer, array)
            self.bounds.append(b)
            self.width.append(w)
            reads_plain = np.array([-(-(hi - lo) // 2**array.adc_bits) for lo, hi in b])
            self.base.append(array.adc_share * array.input_bits * reads_plain)
            per_block = []
            for lo, hi in b:
                ones = np.unpackbits(q[:, lo:hi, None], axis=2).sum(axis=1, dtype=np.int64)
                reads = np.maximum(1, -(-ones // 2**array.adc_bits))
                per_block.append(array.adc_share * reads.sum(axis=1))
            self.zskip.append(np.stack(per_block, axis=1))  # (S, B) cycles
        self.n_arrays = sum(len(b) * w for b, w in zip(self.bounds, self.width))

    @property
    def min_pes(self) -> int:
        return -(-self.n_arrays // self.arrays_per_pe)

    def cycles(self, layer: int, zskip: bool) -> np.ndarray:
        """(S, B) integer cycles per sampled patch and block."""
        if zskip:
            return self.zskip[layer]
        s = self.q[layer].shape[0]
        return np.broadcast_to(self.base[layer], (s, len(self.base[layer])))


# ------------------------------------------------------------ first layer
def calibration_image(config: dict) -> np.ndarray:
    """The calibration image (1, hw, hw, 3) float32 in [0, 1]: a cubic-resized
    8x8 uniform field plus 0.08 normal noise, scaled to [0, 1] per image, from
    ``jax.random`` keys split off ``profile.seed`` on the host CPU.  The
    recipe and key order are the program's documented calibration input,
    made here again with jax alone."""
    import jax

    p = config["profile"]
    hw = int(config["input_hw"])
    with jax.default_device(jax.devices("cpu")[0]):
        kimg, _ = jax.random.split(jax.random.PRNGKey(p["seed"]))
        k1, k2 = jax.random.split(kimg)
        n = int(p["images"])
        coarse = jax.random.uniform(k1, (n, 8, 8, 3))
        smooth = jax.image.resize(coarse, (n, hw, hw, 3), method="cubic")
        noisy = np.asarray(smooth + 0.08 * jax.random.normal(k2, (n, hw, hw, 3)))
    lo = noisy.min(axis=(1, 2, 3), keepdims=True)
    hi = noisy.max(axis=(1, 2, 3), keepdims=True)
    return ((noisy - lo) / (hi - lo + np.float32(1e-9))).astype(np.float32)


def first_layer_samples(config: dict, f=np.float32) -> np.ndarray:
    """The first conv layer's sampled quantized patch rows (take, k*k*cin):
    im2col of the calibration image (zero 'SAME' padding, rows ordered
    channel, then kernel row, then kernel column), per-tensor uint8
    quantization at scale max/255, and the rows drawn by
    ``default_rng(0).choice(patches, take, replace=False)``.  ``f`` is the
    arithmetic of the quantization: float32 as the configuration states, or
    a lower one for the control."""
    layer = layers_of(config)[0]
    x = calibration_image(config)
    n, hw, _, cin = x.shape
    k, s, out = layer.kernel, layer.stride, layer.out_hw
    pad = max((out - 1) * s + k - hw, 0)
    lo = pad // 2
    xp = np.zeros((n, hw + pad, hw + pad, cin), np.float32)
    xp[:, lo:lo + hw, lo:lo + hw] = x
    cols = np.empty((n, out, out, cin, k, k), np.float32)
    for i in range(k):
        for j in range(k):
            cols[..., i, j] = xp[:, i:i + s * out:s, j:j + s * out:s]
    pat = np.maximum(cols.reshape(n * out * out, cin * k * k), 0)
    scale = np.float32(np.float64(pat.max()) / 255.0 + 1e-12)
    q = np.clip(np.round((pat.astype(f) / f(scale)).astype(np.float32)), 0, 255).astype(np.uint8)
    take = min(int(config["profile"]["sample_patches"]), q.shape[0])
    sel = np.random.default_rng(0).choice(q.shape[0], size=take, replace=False)
    return q[sel]


# ------------------------------------------------------------- allocation
def proportional(weight, cost, budget, f):
    weight, cost = np.asarray(weight, f), np.asarray(cost, f)
    rep = np.ones(weight.size, dtype=np.int64)
    if budget <= 0:
        return rep
    share = weight / weight.sum() * f(budget)
    extra = np.floor(share / cost).astype(np.int64)
    rep += extra
    left = f(budget) - (extra * cost).sum()
    frac = share / cost - extra
    for i in np.argsort(-frac):
        if cost[i] <= left:
            rep[i] += 1
            left -= cost[i]
    return rep


def greedy(base, cost, budget, f):
    base, cost = np.asarray(base, f), np.asarray(cost, f)
    rep = np.ones(base.size, dtype=np.int64)
    heap = [(-base[i], i) for i in range(base.size)]
    heapq.heapify(heap)
    left = f(budget)
    while heap:
        _, i = heap[0]
        if cost[i] > left:
            break
        heapq.heappop(heap)
        left -= cost[i]
        rep[i] += 1
        heapq.heappush(heap, (-(base[i] / f(rep[i])), i))
    return rep


@dataclass(frozen=True)
class Design:
    policy: str
    replicas: list  # per layer: (1,) layer-wise or (B,) block-wise counts
    arrays_used: int
    arrays_total: int


def allocate(net: Network, policy: str, n_pes: int, f=np.float64) -> Design:
    total = n_pes * net.arrays_per_pe
    budget = total - net.n_arrays
    if budget < 0:
        raise ValueError(f"{n_pes} PEs cannot hold {net.n_arrays} arrays")
    layer_arrays = [len(b) * w for b, w in zip(net.bounds, net.width)]
    L = len(net.layers)
    if policy in ("baseline", "weight_based"):
        rep = proportional([l.macs for l in net.layers], layer_arrays, budget, f)
    elif policy == "perf_layerwise":
        lat = [net.cycles(i, True).max(axis=1).astype(f).mean() * f(net.layers[i].patches)
               for i in range(L)]
        rep = greedy(lat, layer_arrays, budget, f)
    elif policy == "blockwise":
        lat, cost = [], []
        for i in range(L):
            mean = net.cycles(i, True).astype(f).mean(axis=0)
            lat.extend(mean * f(net.layers[i].patches))
            cost.extend([net.width[i]] * len(net.bounds[i]))
        flat = greedy(lat, cost, budget, f)
        per_layer, k = [], 0
        for b in net.bounds:
            per_layer.append(flat[k:k + len(b)])
            k += len(b)
        used = net.n_arrays + int(((flat - 1) * np.asarray(cost)).sum())
        return Design(policy, per_layer, used, total)
    else:
        raise ValueError(policy)
    used = net.n_arrays + int(((rep - 1) * np.asarray(layer_arrays)).sum())
    return Design(policy, [rep[i:i + 1] for i in range(L)], used, total)


# -------------------------------------------------------------- throughput
def evaluate(net: Network, d: Design, f=np.float64) -> dict:
    """Total cycles for ``eval_images`` images, images/s, mean utilization."""
    n = f(net.eval_images)
    layer_T, util = [], []
    for i, layer in enumerate(net.layers):
        cyc = net.cycles(i, d.policy != "baseline").astype(f)
        P = f(layer.patches) * n
        busy = cyc.mean(axis=0).sum() * P * f(net.width[i])
        if d.policy in LAYERWISE:
            r = f(d.replicas[i][0])
            slow = cyc.max(axis=1)
            t = max(slow.mean() * P / r, slow.max())
            alive = f(len(net.bounds[i]) * net.width[i]) * r
        else:
            r = d.replicas[i].astype(f)
            t = np.maximum(cyc.mean(axis=0) * P / r, cyc.max(axis=0)).max()
            alive = (r * f(net.width[i])).sum()
        layer_T.append(t)
        util.append((busy, alive))
    T = max(layer_T)
    u = [b / (a * T) for b, a in util]
    return {
        "total_cycles": f(T),
        "images_per_sec": n / (f(T) / f(net.clock_hz)),
        "mean_utilization": f(np.mean(np.asarray(u, f))),
        "arrays_used": d.arrays_used,
        "arrays_total": d.arrays_total,
    }


# ------------------------------------------------------------------ replay
def service_indices(seed: int, samples: list[int], patches: list[int], n: int):
    """Per-layer (n, patches) sample rows for each request's patch jobs, drawn
    layer-major from ``default_rng(seed)``.  The draw order is the program's
    documented behaviour (every engine of it consumes the same rows), so the
    reference makes the same draws."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, s, size=(n, p)) for s, p in zip(samples, patches)]


def replay(net: Network, designs: list[Design], arrivals: np.ndarray,
           idx: list[np.ndarray], f=np.float64) -> np.ndarray:
    """Completion times (C, N) of every design's requests.

    ``arrivals`` (C, N) in cycles; ``idx`` from ``service_indices``.  Every
    design runs in one array: pools padded to the widest layer, replicas
    padded with servers that are never free."""
    C, N = arrivals.shape
    stages = []
    for i in range(len(net.layers)):
        tabs, reps = [], []
        for d in designs:
            cyc = net.cycles(i, d.policy != "baseline")
            if d.policy in LAYERWISE:
                tabs.append(cyc.max(axis=1, keepdims=True))
            else:
                tabs.append(cyc)
            reps.append(np.asarray(d.replicas[i]))
        pools = max(t.shape[1] for t in tabs)
        lanes = max(int(r.max()) for r in reps)
        S = tabs[0].shape[0]
        svc = np.zeros((S, C, pools), f)
        mask = np.zeros((C, pools), bool)
        free = np.full((C, pools, lanes), np.inf, f)
        for c, (t, r) in enumerate(zip(tabs, reps)):
            svc[:, c, : t.shape[1]] = t
            mask[c, : t.shape[1]] = True
            for b, k in enumerate(r):
                free[c, b, :k] = 0
        stages.append((svc, mask, free))
    out = np.zeros((C, N), f)
    for r in range(N):
        t = arrivals[:, r].astype(f)
        for i, (svc, mask, free) in enumerate(stages):
            pools = mask.shape[1]
            rows_i, cols_i = np.arange(C)[:, None], np.arange(pools)[None, :]
            done = t.copy()
            for s in idx[i][r]:
                lane = free.argmin(axis=2)  # earliest-free replica of each pool
                start = np.maximum(free[rows_i, cols_i, lane], t[:, None])
                end = start + svc[s]
                free[rows_i, cols_i, lane] = np.where(mask, end, np.inf)
                done = np.maximum(done, np.where(mask, end, -np.inf).max(axis=1))
            t = done
        out[:, r] = t
    return out
