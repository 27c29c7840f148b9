"""Job kind ``sweep``: ``repro.dse.run_fused_sweep`` over a (geometry x ADC)
x policy x PE-budget grid.  Work: designs evaluated, every one distinct."""

from __future__ import annotations

import numpy as np

import traffic


def budgets(mix: dict, min_pes: int) -> range | list[int]:
    """Every whole PE count in ``pe_multiplier`` x min PEs, each once; with
    ``pe_points`` (at least 2), that many counts spaced evenly between the
    same two ends, both in, each once (fewer where rounding merges two)."""
    lo, hi = (float(x) for x in mix["pe_multiplier"])
    lo_n, hi_n = max(min_pes, int(np.ceil(min_pes * lo))), int(np.ceil(min_pes * hi))
    if "pe_points" not in mix:
        return range(lo_n, hi_n + 1)
    n = mix["pe_points"]
    if not isinstance(n, int) or n < 2:
        raise SystemExit(f"pe_points must be a whole number of at least 2, not {n!r}")
    return [int(x) for x in np.unique(np.rint(np.linspace(lo_n, hi_n, n)))]


def order(n: int, seed: int, j: int) -> np.ndarray:
    """Job ``j``'s order of the grid's ``n`` designs: the same designs in
    every job and for every seed, in an order drawn from ``(seed, j)``."""
    return traffic.rng(seed, j).permutation(n)


class Job:
    rate_metric = "dse_configs_per_s"
    # readings behind each limit: PERF.md, section 2
    limits = {"discrete_mismatch": 0, "float_rel_err": 2e-8}

    def __init__(self, config: dict, mix: dict, seed: int):
        from repro.core import cim
        from repro.dse import SweepPoint, run_fused_sweep

        self.config, self.mix, self.seed = config, mix, seed
        self.ref = ref = traffic.reference_of(config)
        self.run_sweep = run_fused_sweep
        base = ref.Array(**config["array"])
        self.variants = [(r, a) for r in mix["rows"] for a in mix["adc_bits"]]
        self.policies = list(mix["policies"])
        # the grid, built once: (variant, policy, PE count) of every design
        self.min_pes, self.budgets, self.grid, self.points = [], [], [], []
        for v, (rows, adc) in enumerate(self.variants):
            arr = cim.DEFAULT_ARRAY.variant(rows=rows, cols=rows, adc_bits=adc)
            spec = cim.with_array(getattr(cim, config["spec"])(), arr)
            if (rows, adc) == (base.rows, base.adc_bits):
                traffic.check_spec(config, spec)
            m = ref.min_pes(config, base.variant(rows, adc))
            if m != spec.min_pes(config["arrays_per_pe"]):
                raise SystemExit(f"min PEs {m} != the program's {spec.min_pes(config['arrays_per_pe'])}")
            self.min_pes.append(m)
            self.budgets.append(budgets(mix, m))
            for n in self.budgets[-1]:
                for p, pol in enumerate(self.policies):
                    self.grid.append((v, p, n))
                    self.points.append(SweepPoint(config["network"], pol, n, arr))
        self.index = {g: i for i, g in enumerate(self.grid)}
        if len(set(self.points)) != len(self.points):
            raise SystemExit("the sweep grid names a design twice")
        self.run(self.inputs(traffic.WARMUP))

    def inputs(self, j: int) -> np.ndarray:
        return order(len(self.points), self.seed, j)

    def run(self, perm: np.ndarray) -> dict:
        res = self.run_sweep([self.points[i] for i in perm])
        return {
            "total_cycles": res.total_cycles,
            "images_per_sec": res.images_per_sec,
            "mean_utilization": res.mean_utilization,
            "arrays_used": res.arrays_used,
            "arrays_total": res.arrays_total,
        }

    def work(self, out: dict) -> int:
        return int(out["total_cycles"].shape[0])

    def families(self, jobs: int) -> list[tuple[int, int]]:
        """(configs, replica-vector length) per eval program family, for
        ``jobs`` jobs: per-layer vectors for the layer-wise policies, one
        entry per block for ``blockwise``."""
        ref, out = self.ref, []
        L = len(self.config["layers"])
        base = ref.Array(**self.config["array"])
        for (rows, adc), b in zip(self.variants, self.budgets):
            blocks = ref.n_blocks(self.config, base.variant(rows, adc))
            for pol in self.policies:
                out.append((len(b) * jobs, blocks if pol == "blockwise" else L))
        return out

    def compare(self, kept: list, control: bool = False) -> dict:
        """Sampled designs of the window against the float64 reference.  With
        ``control`` the float32 reference stands in the program's place."""
        ref = self.ref
        q = traffic.capture_samples(self.config)
        base = ref.Array(**self.config["array"])
        k = self.mix["check_configs_per_variant_policy"]
        rng = traffic.rng(self.seed, traffic.SAMPLE)
        mism = checked = failed = 0
        worst = 0.0
        for v, (rows, adc) in enumerate(self.variants):
            net = ref.Network(self.config, q, base.variant(rows, adc))
            for p, pol in enumerate(self.policies):
                for _ in range(k):
                    _, perm, out = kept[int(rng.integers(len(kept)))]
                    n_pes = int(rng.choice(self.budgets[v]))
                    want = ref.evaluate(net, ref.allocate(net, pol, n_pes))
                    if control:
                        f = np.float32
                        got = ref.evaluate(net, ref.allocate(net, pol, n_pes, f), f)
                    else:
                        i = int(np.flatnonzero(perm == self.index[(v, p, n_pes)])[0])
                        got = {c: out[c][i] for c in want}
                    checked += 1
                    bad = (int(got["arrays_used"]), int(got["arrays_total"])) != (
                        want["arrays_used"], want["arrays_total"]
                    )
                    mism += bad
                    for c in ("total_cycles", "images_per_sec", "mean_utilization"):
                        err = abs(float(got[c]) / float(want[c]) - 1.0)
                        err = err if np.isfinite(err) else np.inf
                        worst = max(worst, err)
                        bad |= err > self.limits["float_rel_err"]
                    failed += bad
        return {"checked": checked, "failed": failed,
                "discrete_mismatch": mism, "float_rel_err": worst}

    def sound(self, out: dict) -> int:
        """Configs whose columns are not finite and positive."""
        ok = np.ones(self.work(out), bool)
        for c in ("total_cycles", "images_per_sec", "mean_utilization"):
            ok &= np.isfinite(out[c]) & (out[c] > 0)
        ok &= out["arrays_used"] <= out["arrays_total"]
        return int((~ok).sum())
