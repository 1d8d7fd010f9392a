"""The one place the suite builds a tiny served family.

A test file keeps its sizes as data (``BUILD``, ``CFG``) and describes
its family once: ``Family(runner, CFG, ref, probe)``. What it asks of
it says what the test does with the engine:

- ``shared(...)`` — for tests that only READ (logits, program text,
  counters of a request they admit and release): built and warmed once
  a worker process for a given (runner, configuration, seed, patched
  thresholds), whichever module asks. A test that takes it leaves every
  slot released.
- ``fresh(...)`` — a new ``build_engine`` call, for a test that needs
  an engine nobody wrote to, or one traced under a fault of its own.

Both go through the runner's own ``build_engine``: the cell's path is
what the tests test. Nothing here clears a cache of JAX's: every engine
lowers through ``CompiledBlock``s of its own, and a trace made under a
patch is keyed by functions that die with that engine.
"""

import json

import jax
import numpy as np
import pytest

from paddle_tpu.ops import expert_ffn

_shared = {}

# ``patches`` of a family whose every call of the expert layer goes through
# the grouped product (at a tiny size each is under the threshold and would
# take the dense way)
GROUPED = ((expert_ffn, "DENSE_MAX_TOKENS", 0),)


class Family:
    """One tiny family: the runner module that builds its engine, the
    configuration, the plain reference it is compared with, the probe
    its runner reads logits through, and what the file does to a built
    engine before the warm-up (``prepare(engine, build, seed)``)."""

    def __init__(self, runner, cfg, ref=None, probe=None, prepare=None):
        self.runner, self.cfg, self.ref = runner, cfg, ref
        self._probe, self.prepare = probe, prepare

    def build(self, **changes) -> dict:
        return {**self.cfg["build"], **changes}

    def fresh(self, seed=5, patches=(), warm=True, **changes):
        """A new engine of ``build(**changes)`` with the weights of
        ``seed``, built and warmed under ``patches`` — ``((module,
        attribute, value), ...)``: the programs are traced and compiled
        inside ``warmup()``, under the thresholds set here; later
        dispatches reuse the executables."""
        build = self.build(**changes)
        with pytest.MonkeyPatch.context() as patch:
            for module, attr, value in patches:
                patch.setattr(module, attr, value)
            engine = self.runner.build_engine({**self.cfg, "build": build},
                                              seed, jax.devices()[0])
            if self.prepare is not None:
                self.prepare(engine, build, seed)
            if warm:
                engine.warmup()
        return engine

    def shared(self, seed=5, patches=(), warm=True, **changes):
        """The worker's one engine of this (runner, configuration, seed,
        patches): the same object for every asker."""
        key = (self.runner.__name__,
               json.dumps({**self.cfg, "build": self.build(**changes)},
                          sort_keys=True),
               seed, warm, getattr(self.prepare, "__qualname__", None),
               tuple((module.__name__, attr, repr(value))
                     for module, attr, value in patches))
        if key not in _shared:
            _shared[key] = self.fresh(seed, patches, warm, **changes)
        return _shared[key]

    def params_of(self, engine, build=None) -> dict:
        names = self.ref.param_names(build or self.cfg["build"])
        return {n: engine.scope.find_var(n) for n in names}

    def probe(self, engine):
        """The engine's one probe (its readers compile at their first
        call, once an engine and not once a request)."""
        if "_families_probe" not in vars(engine):
            engine._families_probe = self._probe(engine)
        return engine._families_probe

    def request(self, engine, prompt_len, max_new, seed=1, build=None):
        """One greedy request through the runner's ``serve_one``:
        (prompt, *what it served)."""
        vocab = (build or self.cfg["build"])["vocab"]
        prompt = np.random.RandomState(seed).randint(1, vocab, prompt_len)
        served = self.runner.serve_one(engine, prompt, max_new,
                                       probe=self.probe(engine))
        assert len(served[0]) == max_new
        return (prompt, *served)
