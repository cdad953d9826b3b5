"""Run the tiny four-chip training cell on four virtual CPU devices, sound
or with the exchange between chips left out; print ``correct``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python tests/bench/dp4_cpu_run.py [sound|no_exchange]
"""
import json
import sys

import conftest  # noqa: F401  (puts the repository root on sys.path)


def main(fault: str) -> None:
    import jax
    from bench.lib import program, runner
    from bench.lib.peaks import PEAKS
    from repro.train import steps
    runner.peaks_for = lambda kind: PEAKS["TPU v5 lite"]
    program.use_compile_cache = lambda: "off"
    cell = conftest.tiny_cell("train.repro-100m.dp4.rq8ef")
    if fault == "no_exchange":
        real = steps.make_train_step

        def broken(mc, opt, scfg):
            step = real(mc, opt, scfg)
            mesh = jax.sharding.get_abstract_mesh()

            def local(state, batch):
                # every chip steps on its own rows: no gradient exchange
                return jax.shard_map(
                    step, mesh=mesh,
                    in_specs=(jax.sharding.PartitionSpec(),
                              jax.sharding.PartitionSpec("data")),
                    out_specs=jax.sharding.PartitionSpec(),
                    check_vma=False)(state, batch)
            return local
        steps.make_train_step = broken
    res = runner.run_cell(cell, seed=777, seconds=1.5, trace=False,
                          devices=jax.devices()[:4], t_process=0.0)
    print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                      "count": res["device"]["count"]}))


if __name__ == "__main__":
    main(sys.argv[1])
