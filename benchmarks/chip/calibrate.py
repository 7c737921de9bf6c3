"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,...,12 --control-seeds 1,2,3 [--out <file.json>]

For every seed: the program's first rounds (through the trainer's own
``run()``, as a benchmark run drives them) against the float32 reference.
For every control seed besides: the reference put in the program's place
and computed one precision below the configuration's (float8 e4m3 operands
for a bfloat16 model), and the same reference with each fault planted that
a training cell can have: half of the batch left out, one label token of
every row altered, and (with several clients) the exchange between chips
left out.  A state left unchanged reads 1 on
``update_gap`` by construction and needs no run.

All in one process, on the chip the cell asks for; the benchmark's own
runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def faults(cell):
    """Planted faults, as changes to the batches the reference sees."""
    import numpy as np

    # batches are (clients, B, S)
    def half(batches):
        rows = batches[0]["tokens"].shape[1]
        if rows > 1:
            return [{k: v[:, :rows // 2] for k, v in b.items()}
                    for b in batches]
        seq = batches[0]["tokens"].shape[2]
        return [{k: v[:, :, :seq // 2] for k, v in b.items()}
                for b in batches]

    def token(batches):
        vocab = cell.config["model"]["vocab"]
        out = []
        for b in batches:
            labels = np.array(b["labels"])
            labels[:, :, 0] = (labels[:, :, 0] + 1) % vocab
            out.append({"tokens": b["tokens"], "labels": labels})
        return out

    out = {"half_batch": (half, True), "token_altered": (token, True)}
    if cell.traffic["clients"] > 1:
        out["exchange_left_out"] = (lambda b: b, False)
    return out


def memory(jax, trainer, devs):
    """The round step's compiled memory analysis beside the devices'
    ``peak_bytes_in_use`` after the compared rounds."""
    import jax.numpy as jnp
    import numpy as np
    step = trainer._step_for(trainer._schedule())
    n, t = trainer.n, trainer
    batch = {k: jnp.asarray(v if n > 1 else v[0]) for k, v in
             t.data.global_batch(n, t.batch_per_client, t.seq, 0).items()}
    with trainer.mesh:
        ma = step.lower(trainer.state, batch,
                        jnp.ones((n,), jnp.float32)).compile() \
            .memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "alias_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes")
    return {"compiled": {f: int(getattr(ma, f, -1)) for f in fields},
            "peak_bytes_in_use": [int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", -1)) for d in devs],
            "bytes_in_use": [int((d.memory_stats() or {}).get(
                "bytes_in_use", -1)) for d in devs]}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-chip", action="store_true",
                    help="allow a run without a TPU (rehearsal)")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}

    import jax
    import jax.numpy as jnp
    from chipbench import check, clock, harness
    from chipbench.cell import CHECKOUT, load_cell
    cell = load_cell(args.workload)
    devs = harness.device_of(jax, cell.chips, not args.no_chip)
    out = {"workload": args.workload, "device": devs[0].device_kind,
           "program": {}, "control": {}, "faults": {}}
    for seed in seeds:
        t0 = time.perf_counter()
        trainer, params0 = harness.build(cell, seed, devs)
        rc = clock.RoundClock(trainer)
        prog, batches = harness.first_rounds(trainer, rc, cell, seed, params0)
        if "memory" not in out:
            out["memory"] = memory(jax, trainer, devs)
            print("memory: " + json.dumps(out["memory"]), flush=True)
        rc.close()
        del trainer, rc
        gc.collect()
        t1 = time.perf_counter()
        ref = harness.reference(cell, seed, devs, batches, prog["weights"])
        t2 = time.perf_counter()
        nums = check.compare(prog, ref)
        nums.update(program_loss=prog["loss"], reference_loss=ref["loss"],
                    program_s=t1 - t0, reference_s=t2 - t1)
        out["program"][seed] = nums
        print(f"seed {seed}: " + json.dumps(nums), flush=True)
        if seed in control:
            ctl = harness.reference(cell, seed, devs, batches,
                                    prog["weights"],
                                    precision="float8")
            out["control"][seed] = check.compare(ctl, ref)
            print(f"control {seed}: " + json.dumps(out["control"][seed]),
                  flush=True)
            for name, (plant, exchange) in faults(cell).items():
                bad = harness.reference(cell, seed, devs, plant(batches),
                                        prog["weights"], exchange=exchange)
                out["faults"].setdefault(name, {})[seed] = \
                    check.compare(bad, ref)
                print(f"fault {name} {seed}: "
                      + json.dumps(out["faults"][name][seed]), flush=True)
        gc.collect()
    for name in check.NUMBERS:
        vals = [v[name] for v in out["program"].values()]
        ctl = [v[name] for v in out["control"].values()]
        print(f"{name}: program max {max(vals)!r} over {len(vals)} seeds"
              + (f"; control min {min(ctl)!r}" if ctl else ""), flush=True)
    path = args.out or str(CHECKOUT / ".chipbench_out" /
                           f"calibrate.{args.workload}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
