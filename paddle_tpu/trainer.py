"""Event-driven trainer loop — the paddle.v2 capability surface
(reference: python/paddle/v2/trainer.py SGD class with
train(reader, num_passes, event_handler, feed_order), test(); events in
python/paddle/v2/event.py: BeginPass/EndPass/BeginIteration/EndIteration
with cost/metrics payloads; the later fluid Trainer mirrored the same
shape). SURVEY L7 note: v2-unique capabilities are delivered once in the
modern stack — this trainer drives the compiled-program executor, not a
GradientMachine."""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np



class BeginPass:
    def __init__(self, pass_id):
        self.pass_id = pass_id


class EndPass:
    def __init__(self, pass_id, metrics):
        self.pass_id = pass_id
        self.metrics = metrics


class BeginIteration:
    def __init__(self, pass_id, batch_id):
        self.pass_id = pass_id
        self.batch_id = batch_id


class EndIteration:
    def __init__(self, pass_id, batch_id, cost, metrics):
        self.pass_id = pass_id
        self.batch_id = batch_id
        self.cost = cost
        self.metrics = metrics


class SGD:
    """reference: paddle.v2.trainer.SGD — construct with the built cost
    program, then .train(reader, event_handler). Here the cost/optimizer
    live in a fluid Program pair built by the caller (the modern two-
    program convention replaces v2's topology+parameters)."""

    def __init__(self, cost, main_program=None, startup_program=None,
                 place=None, extra_fetch: Optional[Dict[str, str]] = None):
        import paddle_tpu.fluid as fluid
        self._fluid = fluid
        self.cost = cost
        self.main = main_program or fluid.default_main_program()
        self.startup = startup_program or fluid.default_startup_program()
        self.exe = fluid.Executor(place or fluid.TPUPlace())
        self.extra_fetch = extra_fetch or {}
        self._initialized = False
        self._cached_test_prog = None

    def _init(self):
        if not self._initialized:
            self.exe.run(self.startup)
            self._initialized = True

    def _feed_dict(self, batch, feed_order: Optional[List[str]]):
        if not feed_order:
            raise ValueError(
                "feed_order is required: the column order of reader samples "
                "-> feed names (the v2 reference inferred it from the "
                "topology; Program feeds are unordered here)")
        cols = list(zip(*batch))
        return {name: np.asarray(col)
                for name, col in zip(feed_order, cols)}

    def train(self, reader: Callable, num_passes: int = 1,
              event_handler: Optional[Callable] = None,
              feed_order: Optional[List[str]] = None):
        """reader: batch reader (yields lists of sample tuples, e.g. from
        paddle_tpu.reader.batch(...)); feed_order maps sample columns to
        feed names."""
        self._init()
        event_handler = event_handler or (lambda e: None)
        fetch = [self.cost.name] + list(self.extra_fetch.values())
        for pass_id in range(num_passes):
            event_handler(BeginPass(pass_id))
            costs = []
            for batch_id, batch in enumerate(reader()):
                event_handler(BeginIteration(pass_id, batch_id))
                feed = self._feed_dict(batch, feed_order)
                # the executor records each step: the executor.run span
                # under an active profiler, the step-stats sample
                # (steps/s, examples/s, MFU gauges) when observability
                # is enabled
                vals = self.exe.run(self.main, feed=feed,
                                    fetch_list=fetch)
                cost = float(np.asarray(vals[0]).reshape(()))
                costs.append(cost)
                metrics = {k: np.asarray(v) for k, v in
                           zip(self.extra_fetch, vals[1:])}
                event_handler(EndIteration(pass_id, batch_id, cost,
                                           metrics))
            event_handler(EndPass(pass_id,
                                  {"mean_cost": float(np.mean(costs))
                                   if costs else float("nan")}))

    def _test_program(self, feed_order: List[str]):
        """Cost-only eval program: clone(for_test) then prune away the
        backward/optimizer ops so test() can never mutate parameters."""
        if self._cached_test_prog is None:
            from paddle_tpu.core import ir
            cloned = self.main.clone(for_test=True)
            pruned_block = ir.prune_block(cloned.desc.global_block,
                                          [self.cost.name],
                                          list(feed_order))
            cloned.desc.blocks = [pruned_block]
            cloned.desc.bump_version()
            self._cached_test_prog = cloned
        return self._cached_test_prog

    def test(self, reader: Callable, feed_order: Optional[List[str]] = None,
             test_program=None):
        """Average cost over a test reader (reference: v2 trainer.test).
        Evaluation runs a pruned cost-only program — never the optimizer."""
        self._init()
        prog = test_program or self._test_program(feed_order or [])
        costs = []
        for batch in reader():
            feed = self._feed_dict(batch, feed_order)
            (c,) = self.exe.run(prog, feed=feed,
                                fetch_list=[self.cost.name])
            costs.append(float(np.asarray(c).reshape(())))
        return {"mean_cost": float(np.mean(costs)) if costs else
                float("nan")}
