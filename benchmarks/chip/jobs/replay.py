"""Job kind ``replay``: ``VirtualTimeFabric.run_batch`` of the Figure-8
designs under open Poisson arrivals at a fraction of each design's analytic
capacity.  Work: requests replayed, summed over designs."""

from __future__ import annotations

import numpy as np

import traffic


def poisson_arrivals(rates: np.ndarray, n: int, seed: int, j: int) -> tuple[np.ndarray, int]:
    """Job ``j``'s arrival times (designs, n) in cycles, common exponential
    gaps scaled to each design's rate per cycle, and its service-draw seed."""
    rng = traffic.rng(seed, j)
    gaps = rng.exponential(1.0, size=n)
    times = np.cumsum(gaps[None, :] / np.asarray(rates)[:, None], axis=1)
    return times, int(rng.integers(2**31))


class Job:
    rate_metric = "replay_requests_per_s"
    # readings behind each limit: PERF.md, section 2
    limits = {"completion_mismatch": 0, "percentile_rel_err": 0.0}

    def __init__(self, config: dict, mix: dict, seed: int):
        from repro.core.cim import allocate
        from repro.dse.sweep import get_profiled
        from repro.fabric import TraceReplay, VirtualTimeFabric

        self.config, self.mix, self.seed = config, mix, seed
        self.ref = ref = traffic.reference_of(config)
        self.trace_replay = TraceReplay
        spec, prof = get_profiled(config["network"], **traffic.profile_args(config))
        traffic.check_spec(config, spec)
        q = traffic.capture_samples(config)
        self.net = ref.Network(config, q, ref.Array(**config["array"]))
        self.policies = list(mix["policies"])
        self.n_pes = int(np.ceil(self.net.min_pes * mix["pe_multiplier"]))
        self.allocs = [allocate(spec, prof, pol, self.n_pes) for pol in self.policies]
        # arrival rates come from the reference's analytic capacity of each
        # design, so the program is handed arrival times and nothing else
        designs = [ref.allocate(self.net, pol, self.n_pes) for pol in self.policies]
        caps = [ref.evaluate(self.net, d)["images_per_sec"] for d in designs]
        self.rates = np.array([mix["load"] * c / config["clock_hz"] for c in caps])
        per_request = sum(l.patches for l in self.net.layers)
        if per_request != config["expect"]["patch_jobs_per_request"]:
            raise SystemExit(f"{per_request} patch jobs per request, expected "
                             f"{config['expect']['patch_jobs_per_request']}")
        self.n = max(1, round(mix["patch_jobs_per_job"] / per_request))
        self.vt = VirtualTimeFabric(spec, prof)
        self.run(self.inputs(traffic.WARMUP))

    def inputs(self, j: int) -> tuple[np.ndarray, int]:
        return poisson_arrivals(self.rates, self.n, self.seed, j)

    def run(self, inp) -> dict:
        times, s = inp
        res = self.vt.run_batch(self.allocs, [self.trace_replay(t) for t in times], seed=s)
        return {"completions": res.completions, "percentiles": res.percentiles,
                "arrivals": res.arrivals}

    def work(self, out: dict) -> int:
        return int(out["completions"].size)

    def patch_jobs(self, jobs: int) -> int:
        """Simulated (design, request, patch) steps in ``jobs`` jobs."""
        return jobs * len(self.policies) * self.n * self.config["expect"]["patch_jobs_per_request"]

    def _reference(self, inp, f):
        times, s = inp
        ref = self.ref
        idx = ref.service_indices(
            s, [q.shape[0] for q in self.net.q], [l.patches for l in self.net.layers], self.n
        )
        designs = [ref.allocate(self.net, pol, self.n_pes, f) for pol in self.policies]
        return ref.replay(self.net, designs, times, idx, f).astype(np.float64)

    def compare(self, kept: list, control: bool = False) -> dict:
        """Every completion and percentile of the sampled jobs against the
        float64 reference.  With ``control`` the float32 reference stands in
        the program's place."""
        rng = traffic.rng(self.seed, traffic.SAMPLE)
        pick = rng.choice(len(kept), size=min(self.mix["check_jobs"], len(kept)), replace=False)
        mism = checked = 0
        worst = 0.0
        for j in sorted(pick):
            _, inp, out = kept[j]
            times = inp[0]
            want = self._reference(inp, np.float64)
            want_pct = np.percentile(want - times, [50.0, 95.0, 99.0], axis=1).T
            if control:
                comp = self._reference(inp, np.float32)
                pct = np.percentile(comp - times, [50.0, 95.0, 99.0], axis=1).T
            else:
                comp, pct = out["completions"], out["percentiles"]
            mism += int((comp != want).sum())
            checked += want.size
            err = np.abs(pct / want_pct - 1.0).max()
            worst = max(worst, float(err) if np.isfinite(err) else np.inf)
        return {"checked": checked, "failed": mism,
                "completion_mismatch": mism, "percentile_rel_err": worst}

    def sound(self, out: dict) -> int:
        c, a = out["completions"], out["arrivals"]
        ok = np.isfinite(c) & (c > a)
        return int((~ok).sum())
