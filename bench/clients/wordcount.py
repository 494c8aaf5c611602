"""Recurring word-count jobs through ``GeoJob.execute``.

Set-up draws ``corpora`` corpora (the configuration's ``word_count``
sizes) and the platform from the traffic's ``data_seed``, splits each
corpus over the platform's sources, makes the job's plan once
(``plan_mode`` at the configuration's solver budget) and runs every corpus
once, so each reducer shape the window meets is compiled or loaded before
it starts.  The window is a closed loop: job after job, cycling through
the corpora in an order drawn from ``--seed``, until ``--seconds`` have
passed.  The job's plan is priced as ``GeoJob.calibrate`` would price it:
the sources' record bytes and the configuration's measured word-count
alpha.

The data is the traffic's and not the run's seed because every reducer
shape is a compile of its own (``segment_sum`` takes the segment count as
a static argument): corpora drawn from each run's seed would compile in
every run's set-up.  Recurring jobs over the same inputs is what this
cell stands for.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from bench import gen, reference
from bench.record import Record

PLATFORM, CORPUS, ORDER = 10, 11, 12  # seed streams of gen.rng


class Client:
    def __init__(self, config: dict, traffic: dict, seed: int, log):
        self.cfg, self.mix, self.seed, self.log = config, traffic, seed, log
        self.wc = config["word_count"]

    def _reduce(self, keys, values):
        """The application's reducer, each call's shape recorded."""
        seg = 1 + int(np.count_nonzero(keys[1:] != keys[:-1]))
        self.calls.append(dict(rows=int(keys.shape[0]), segments=seg,
                               width=1, path=self.path(int(keys.shape[0]))))
        return self.reduce_fn(keys, values)

    def setup(self, seconds: float) -> None:
        from repro.api import GeoJob
        from repro.core.platform import Platform
        from repro.kernels.ops import segment_sum_path
        from repro.mapreduce.apps import word_count

        t = time.perf_counter()
        n_src = len(self.cfg["platform"]["sites"])
        self.corpora = []
        for c in range(int(self.mix["corpora"])):
            keys, values, words = gen.corpus(
                self.wc, gen.rng(self.mix["data_seed"], CORPUS, c))
            per_source = list(zip(np.array_split(keys, n_src),
                                  np.array_split(values, n_src)))
            self.corpora.append((per_source, words))
        self.log(f"set-up: {len(self.corpora)} corpora of "
                 f"{self.corpora[0][1].size} records "
                 f"{time.perf_counter() - t:.3f}s")
        a = gen.planetlab(self.cfg["platform"], 1.0,
                          gen.rng(self.mix["data_seed"], PLATFORM))
        per_source = self.corpora[0][0]
        a["D"] = np.array([k.shape[0] * self.wc["record_bytes"]
                           for k, _ in per_source], np.float64) / 1e6
        a["alpha"] = float(self.wc["alpha"])
        platform = Platform(name="wordcount", **a)
        app = word_count()
        self.reduce_fn, self.path, self.calls = (
            app.reduce_fn, segment_sum_path, [])
        s = self.cfg["solver"]
        t = time.perf_counter()
        self.job = GeoJob(platform, dataclasses.replace(
            app, reduce_fn=self._reduce)).plan(
                mode=self.mix["plan_mode"], barriers=tuple(self.cfg["barriers"]),
                n_restarts=s["n_restarts"], steps=s["steps"], seed=0)
        self.log(f"set-up: plan {time.perf_counter() - t:.3f}s")
        for c, (per_source, _) in enumerate(self.corpora):
            t = time.perf_counter()
            self.job.execute(per_source)
            self.log(f"set-up: warm corpus {c} "
                     f"{time.perf_counter() - t:.3f}s")
        self.order = gen.rng(self.seed, ORDER).permutation(len(self.corpora))

    def window(self, seconds: float) -> Record:
        rec = Record()
        self.calls = []
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            c = int(self.order[n % len(self.corpora)])
            per_source, words = self.corpora[c]
            start = time.perf_counter() - t0
            try:
                with jax.profiler.TraceAnnotation("bench.execute"):
                    outputs = self.job.execute(per_source).outputs
            except Exception as e:  # a raised job fails
                self.log(f"job {n} raised {e!r}")
                outputs = None
            done = time.perf_counter() - t0
            rec.ops.append(dict(kind="wordcount", sent=start, start=start,
                                done=done, work=int(words.size), corpus=c,
                                answer=outputs))
            rec.dispatches.append(dict(kind="wordcount", n=1, start=start,
                                       end=done))
            n += 1
        rec.window_s = time.perf_counter() - t0
        rec.reducer_calls = self.calls
        return rec

    def close(self) -> None:
        self.job = None

    def check(self, rec: Record, limits: dict, control: bool = False) -> dict:
        """Each job's per-word counts against a ``bincount`` of its corpus.

        ``count_mismatch``: the most words any one job counted wrong.
        With ``control`` every job's reducer outputs are recounted in
        bfloat16 (:func:`bench.reference.bf16_sums`) before the comparison.
        """
        vocab = int(self.wc["vocab"])
        expected = [reference.word_counts(w, vocab) for _, w in self.corpora]
        worst = failed = 0
        for op in rec.ops:
            outputs = op["answer"]
            if outputs is None:
                failed += 1
                continue
            counts = np.zeros(vocab, np.int64)
            for k, v in outputs:
                if control:
                    v = reference.bf16_sums(v)
                np.add.at(counts, np.asarray(k), np.asarray(v))
            wrong = int(np.count_nonzero(counts != expected[op["corpus"]]))
            worst = max(worst, wrong)
            failed += wrong > limits["count_mismatch"]
        return dict(failed=failed, numbers=dict(count_mismatch=worst))
