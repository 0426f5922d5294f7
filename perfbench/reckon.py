"""Reckon each configuration's peak HBM on a v5e chip before a chip run:
compile its train step ahead of time for a described (not attached) v5e
and add the buffers a device int8 trigger holds.

    JAX_PLATFORMS=cpu python3 perfbench/reckon.py yi-6b internlm2-20b

Prints one JSON object per configuration:

    step     = the step program's arguments + outputs + temporaries
    S, F     = the train state, and its packed f32 subtree
    full-sync host   step
    device-int8      max(step + the last full's leaves S + packed base F,
                         trigger: live S + S + F + packed new F + q F/4)
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def main(names: list) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import arith
    import harness
    from repro.config import ShapeConfig
    from repro.models import zoo
    from repro.optim import make_optimizer

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    limit = 16_909_336_064       # bytes_limit a v5e chip reports
    for name in names:
        cfg = harness.load_config(name)
        mcfg, ocfg = harness.model_config(cfg), harness.optimizer_config(cfg)
        opt = make_optimizer(ocfg)
        place = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
            s.shape, s.dtype, sharding=chip)
        state = jax.tree_util.tree_map(place, zoo.state_specs(mcfg, opt))
        batch = jax.tree_util.tree_map(place, zoo.input_specs(
            mcfg, ShapeConfig("b", "train", cfg["job"]["seq_len"],
                              cfg["job"]["batch"])))
        ma = jax.jit(zoo.make_train_step(mcfg, opt, ocfg)).lower(
            state, batch).compile().memory_analysis()
        step = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes)
        S = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(state))
        F = arith.packed_groups(cfg) * arith.GROUP * 4
        int8 = max(step + S + F, 2 * S + 2 * F + F // 4)
        print(json.dumps({
            "config": name, "args": ma.argument_size_in_bytes,
            "out": ma.output_size_in_bytes, "temp": ma.temp_size_in_bytes,
            "state": S, "packed_f32": F, "bytes_limit": limit,
            "full_sync_host": step, "full_sync_host_share": step / limit,
            "device_int8": int8, "device_int8_share": int8 / limit}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
