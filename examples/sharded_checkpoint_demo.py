"""Demo: sharded checkpoint + restore-with-resharding.

Run from the repo root:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/sharded_checkpoint_demo.py     # 8-device mesh demo
  python examples/sharded_checkpoint_demo.py         # single-chip (TPU)

Trains an MLP under dp=4 ZeRO (Adam moments sharded over the data axis,
the pserver's sharded-optimizer-state capability), saves only per-device
shards (no full gather), then restores bit-equal under dp=8 and keeps
training — the EDL mesh-reconfiguration loop."""

import os
import shutil
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.core.lowering import CompiledBlock
from paddle_tpu.core.scope import Scope
from paddle_tpu.parallel.mesh import DistributeConfig, make_mesh


def build():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[16], dtype="float32")
        y = layers.data(name="y", shape=[1], dtype="float32")
        pred = layers.fc(layers.fc(x, size=32, act="relu"), size=1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=1e-2).minimize(loss)
    return main, startup, loss


def feeds(step):
    rng = np.random.RandomState(100 + step)
    x = rng.rand(8, 16).astype(np.float32)
    return {"x": x, "y": x.sum(1, keepdims=True) * 0.1}


def main():
    ndev = len(jax.devices())
    save_dp, restore_dp = (4, 8) if ndev >= 8 else (1, 1)
    prog, startup, loss = build()

    def dist(n):
        mesh = make_mesh({"dp": n}, devices=jax.devices()[:n])
        return DistributeConfig(mesh=mesh, data_axis="dp",
                                reduce_strategy="reduce_scatter")

    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    cp = fluid.CompiledProgram(prog).with_sharding(dist(save_dp))
    for s in range(5):
        (lv,) = exe.run(cp, feed=feeds(s), fetch_list=[loss.name],
                        scope=scope)
    print(f"trained 5 steps dp={save_dp} ZeRO, loss "
          f"{float(np.asarray(lv).reshape(())):.4f}")

    names = [vd.name for vd in prog.desc.global_block.vars.values()
             if vd.persistable]
    want = {n: np.asarray(scope.find_var(n)) for n in names}

    d = tempfile.mkdtemp(prefix="sharded_ckpt_")
    try:
        fluid.io.save_vars(None, d, prog, scope=scope, sharded=True)
        shard_files = [f for f in os.listdir(d) if ".s" in f]
        print(f"saved {len(shard_files)} shard files for {len(names)} vars "
              f"(dp={save_dp} writes moments as {save_dp} shards each)")

        scope2 = Scope()
        cb = CompiledBlock(prog.desc, 0, ["x", "y"], [loss.name],
                           dist=dist(restore_dp))
        fluid.io.load_vars(None, d, prog, scope=scope2,
                           sharding_fn=cb.param_sharding)
        ok = all(np.array_equal(np.asarray(scope2.find_var(n)), want[n])
                 for n in names)
        print(f"restore under dp={restore_dp}: bit-equal={ok}")

        cp2 = fluid.CompiledProgram(prog).with_sharding(dist(restore_dp))
        for s in range(5, 10):
            (lv,) = exe.run(cp2, feed=feeds(s), fetch_list=[loss.name],
                            scope=scope2)
        print(f"resumed training dp={restore_dp}, loss "
              f"{float(np.asarray(lv).reshape(())):.4f}")
        print("SHARDED CHECKPOINT:", "OK" if ok else "FAILED")
        return 0 if ok else 1
    finally:
        shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
