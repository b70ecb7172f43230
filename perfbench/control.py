"""Readings that set the limits of ``correct``: the program's own on many
seeds, and the control's, on the chip at the cell's own size.

    python3 perfbench/control.py --workload deep10m-l2.h2-batch128 \
        --seeds 11,12,13 --control-seeds 3 --seconds 6 --out control.jsonl

Each seed builds the cell's index, runs a short window at the cell's own
load, and checks as many of its answers as a benchmark run does against
the reference. For the first ``--control-seeds`` seeds the control is
checked too: the reference itself put in the program's place, with the LUT
products formed one precision step lower (the three-pass bf16 product of
``Precision.HIGH``), judged by the same numbers. Each seed prints one JSON
line with both readings, the reference's time, and recall@10 over the
evaluation set with its per-query spread.
"""
import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import compare, groundtruth, harness, reference as ref  # noqa: E402
from benchlib import traffic as tlib  # noqa: E402


def control_numbers(st, queries, *, k, nprobe, metric, stage_a) -> dict:
    """The comparison's numbers of the control's answers."""
    def answers(precision):
        return ref.run_blocks(functools.partial(
            ref.search_block, st, k=k, nprobe=nprobe, metric=metric,
            stage_a=stage_a, precision=precision), queries, harness.REF_BLOCK)
    ctl_s, ctl_i = answers("bf16x3")
    score = functools.partial(ref.score_of, st, metric=metric,
                              stage_a=stage_a)
    ref_of = ref.run_blocks(score, queries, harness.REF_BLOCK,
                            ctl_i.astype(np.int32))
    return compare.numbers(ctl_s, answers("f32")[0], ref_of, metric)


def readings(files: dict, seeds, *, control_seeds: int, seconds: float,
             require_tpu: bool = True):
    """Yield one reading per seed of the cell whose files are ``files``
    (``harness.cell_files``)."""
    import jax
    devices = jax.devices()
    if require_tpu:
        harness.check_device(devices, files["cell"]["chips"], files["peaks"])
        harness.use_compile_cache()
    prog = harness.import_program()
    conf, trf = files["config"], files["traffic"]
    kw = dict(k=trf["k"], nprobe=trf["nprobe"],
              metric=conf["dataset"]["metric"],
              stage_a=harness.stage_a_precision(devices))
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        built = harness.build(prog, conf, seed, trf["n_pool"], trf["n_eval"],
                              trf["k"])
        engine = harness.make_engine(prog, conf, trf, built.index)
        harness.warm_up(engine, trf, built.pool)
        win = harness.measure(engine, trf, built.pool, seconds)
        ids, scores, qrows, failed = harness.answers(win.served, trf["k"])
        row = {"seed": seed, "answered": len(qrows), "failed": failed,
               "compiles": win.compiles}
        if trf["n_eval"]:
            got = harness.finish_eval(engine, trf, built.pool, win.served)
            rec = groundtruth.recall_per_query(got, built.gt)
            row.update(recall=float(rec.mean()), recall_sigma=float(
                rec.std()), n_eval=len(rec))
        data = engine.index.data
        del engine
        gc.collect()
        st = harness.index_state(data)
        pick = tlib.sample_rows(len(qrows), trf["check_sample"], seed)
        queries = built.pool[qrows[pick]]
        t_ref = time.perf_counter()
        row["program"] = harness.check_answers(
            st, queries, ids[pick], scores[pick], **kw)
        row["program"]["ids_not_once"] = harness.ids_not_once(
            np.asarray(data.ivf.point_ids), conf["dataset"]["n_points"])
        row["reference_s"] = time.perf_counter() - t_ref
        if n < control_seeds:
            t_ctl = time.perf_counter()
            row["control"] = control_numbers(st, queries, **kw)
            row["control_s"] = time.perf_counter() - t_ctl
        row["limits"] = files["limits"]
        del st, data, built
        gc.collect()
        yield row
        harness.log(f"seed {seed} took {time.perf_counter() - t:.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for row in readings(harness.cell_files(args.workload),
                        [int(s) for s in args.seeds.split(",")],
                        control_seeds=args.control_seeds,
                        seconds=args.seconds):
        line = json.dumps(row)
        print(line, flush=True)
        with out.open("a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
