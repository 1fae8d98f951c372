"""A neuro-symbolic reasoning model served through ``deploy()`` and the
front door (``FrontDoor`` -> ``ReasonEngine`` -> compiled schedule ->
Pallas kernels), on RAVEN traffic.

The benchmark makes the model's static VSA tables from the seed and puts
them in place of the program's; the reference gets the same tables.  The
check compares, for a sample of the answers the window served, the answer
log-probabilities with the reference's, computed on the host CPU.
"""

from __future__ import annotations

import time

import numpy as np

from bench import raven, traffic
from bench.references import nvsa as reference
from bench.systems import common
from bench.work import nvsa as work

STATS = ("dispatches", "batches", "fused_groups", "fused_fallback_groups")
WARM_UID = 10 ** 9
SAMPLE = 1024


class System:
    unit = "problem"

    def __init__(self, cell: dict, seed: int, seconds: float):
        self.cell, self.cfg, self.mix = cell, cell["config"], cell["traffic"]
        self.seed, self.seconds = seed, seconds
        self.model = self.cfg["model"]
        self.clock = time.perf_counter   # the serving loop's clock

    # -- set-up --------------------------------------------------------------

    def deploy(self):
        from repro.serve import deploy

        cfg = self.cfg
        self.dep = deploy([self.model],
                          options={self.model: dict(cfg["deploy_options"])},
                          seed=self.seed % 2 ** 31,
                          clock=lambda: self.clock())
        self.engine = self.dep.engines[self.model]
        self.tables = reference.make_tables(cfg, self.seed)
        common.same_layout("consts['books']", self.engine.consts["books"],
                           self.tables)
        self.engine.consts = {**self.engine.consts, "books": self.tables}
        self.arrivals = self._arrivals()

    def _request(self, uid: int, p: dict):
        from repro.serve.reason import ReasonRequest

        return ReasonRequest(uid=uid, context_attrs=p["context_attrs"],
                             candidate_attrs=p["candidate_attrs"],
                             context=p.get("context"),
                             candidates=p.get("candidates"))

    def _arrivals(self):
        from repro.serve.frontdoor import ArrivalRequest

        mix, cfg = self.mix, self.cfg
        image = cfg["image_size"] if mix["payload"] == "panels" else None
        self.pool = [raven.problem(tuple(cfg["attr_sizes"]), self.seed, i,
                                   image_size=image)
                     for i in range(mix["pool"])]
        times = traffic.arrival_times(mix["rate_per_s"], self.seconds,
                                      self.seed)
        perm = traffic.rng(self.seed, 3).permutation(mix["pool"])
        self.payload = perm[np.arange(len(times)) % mix["pool"]]
        return [ArrivalRequest(t=float(t), model=self.model,
                               request=self._request(k, self.pool[j]))
                for k, (t, j) in enumerate(zip(times, self.payload))]

    def warmup(self):
        """Every bucket of the deployment's schedule (``Deployment.warmup``),
        then one burst through the front door: full groups and a flush."""
        from repro.serve.frontdoor import ArrivalRequest

        self.dep.warmup()
        cap = self.engine.admission_cap
        burst = [ArrivalRequest(t=0.0, model=self.model,
                                request=self._request(WARM_UID + i, p))
                 for i, p in enumerate(self.pool[:4 * cap + 1])]
        self.dep.serve(burst)
        self.buckets = tuple(self.engine.cfg.buckets or ())

    # -- the window ----------------------------------------------------------

    def serve(self):
        before = dict(self.engine.stats)
        self.report = self.dep.serve(self.arrivals)
        self.stats = common.stats_delta(before, self.engine.stats, STATS)
        return self.report

    def context(self) -> dict:
        cfg = dict(self.cfg)
        return {"unit": self.unit,
                "flops_per_unit": work.per_problem(cfg),
                "circ_conv": work.circ_conv(cfg),
                "stats": self.stats,
                "served_plan": {"buckets": self.buckets,
                                "max_inflight": self.engine.cfg.max_inflight,
                                "schedule": self.engine.cfg.schedule}}

    def units_done(self, until_s: float, since_s: float = 0.0) -> int:
        """Problems answered in (since_s, until_s] of the window."""
        return sum(1 for l in self.report.latencies
                   if since_s < l.done_s <= until_s)

    # -- the check -----------------------------------------------------------

    def release(self):
        """Keep the sampled answers, then free the program's state."""
        served = self.report.results[self.model]
        self.answered = sorted(served)
        self.missing = len(self.arrivals) - len(served)
        pick = traffic.rng(self.seed, 4).choice(
            len(self.answered), min(SAMPLE, len(self.answered)),
            replace=False)
        self.sample = [self.answered[i] for i in sorted(pick)]
        self.served_logp = np.stack([served[u].answer_logprobs
                                     for u in self.sample])
        del self.dep, self.engine, served
        self.report.results.clear()
        common.free()

    def reference(self, precision: str = "highest",
                  device=None) -> np.ndarray:
        """The reference's answers to the sampled requests, on ``device``
        (default: where the tables are)."""
        import jax

        idx = self.payload[self.sample]
        ctx = np.stack([self.pool[j]["context_attrs"] for j in idx])
        cand = np.stack([self.pool[j]["candidate_attrs"] for j in idx])
        tables = self.tables if device is None else \
            jax.device_put(self.tables, device)
        with jax.default_device(device or jax.devices()[0]):
            return reference.solve(self.cfg, tables, ctx, cand,
                                   precision)[0]

    def control(self) -> np.ndarray:
        """The control's answers to the sampled requests: the reference at
        ``high`` (three bfloat16 passes), put in the program's place on the
        chip."""
        return self.reference("high")

    def check(self, served: np.ndarray | None = None
              ) -> tuple[int, int, dict]:
        """The sampled answers (``served``, else the program's) against the
        reference, by each answer's widest log-probability gap.

        The reference runs on the host CPU, whose float32 products are
        exact: on the chip, XLA's ``highest`` products in the reference's
        direct-form convolution read twice the error of the program's Pallas
        kernel, and a reference computed there would add as much to the gap
        as the program does."""
        import jax

        if not hasattr(self, "truth"):
            self.truth = self.reference(device=jax.devices("cpu")[0])
        served = self.served_logp if served is None else served
        gaps = np.abs(served - self.truth).max(axis=-1)
        limit = self.cfg["limits"]["logprob_gap"]
        checks = {"missing": (self.missing, 0),
                  "logprob_gap": (float(gaps.max()), limit)}
        failed = self.missing + int((gaps > limit).sum())
        return len(self.arrivals), failed, checks
