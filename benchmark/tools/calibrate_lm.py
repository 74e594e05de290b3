"""The upper ends a language-model training cell's limits are set below, on
the chip, at the cell's own size: ``python3 -m benchmark.tools.calibrate_lm
--workload <cell> --seeds a,b``.

``calibrate.py`` reads both ends for the cells of ``drivers/fit.py``; here
the lower end is the result lines of the cell's own runs (``compared``), and
this tool reads what stands above it: for every seed the plain reference
recomputed with every matrix product in fp8 (the CONTROL, the precision
below the configuration's), in bfloat16 (a witness that the configuration's
own precision reads as correct) and, with ``--faults``, each planted fault,
all put in the program's place against the plain reference.  One JSON line
per seed; nothing is compared with a limit here."""
from __future__ import annotations

import argparse
import json


def readings(loaded, devices, seed, stands_in):
    """{name: the numbers compared} when the plain reference, altered as
    ``stands_in``'s ``(name, keywords of fit_lm.reference_norms)`` say,
    stands where the program stood."""
    import importlib
    from .. import compare
    from ..drivers import fit_lm
    cfg, mix = loaded["config"], loaded["traffic"]
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    spec = {n: tuple(s)
            for n, s in ref.param_shapes(fit_lm.model_of(cfg)).items()}
    names = sorted(spec)
    make_w0 = lambda: fit_lm.make_weights(seed, spec, cfg["init"])
    ring = fit_lm.lm_ring(mix, cfg["vocab_size"], seed)
    norms = lambda **kw: fit_lm.reference_norms(
        cfg, mix, devices, make_w0, *ring, names, **kw)
    true = norms()
    row = {}
    for name, kw in stands_in:
        got = norms(**kw)
        row[name], _ = compare.training_numbers(
            got[0], true[0], got[1], true[1], got[2], true[2])
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", action="store_true")
    a = ap.parse_args(argv)

    from .. import harness
    from ..references import lowprec, qwen3_next
    loaded = harness.load_cell(a.workload)
    harness.use_compile_cache()
    devices = harness.find_chip(int(loaded["cell"]["chips"]))
    stands_in = [("control_fp8", {"hooks": (lowprec.q_operand,
                                            lowprec.q_cotangent)}),
                 ("reference_bf16", {"hooks": (lowprec.bf16, lowprec.bf16)})]
    if a.faults:
        stands_in += [("fault_" + f, {"fault": f}) for f in qwen3_next.FAULTS]
    for seed in (int(s) for s in a.seeds.split(",")):
        row = readings(loaded, devices, seed, stands_in)
        print(json.dumps(dict(row, seed=seed)), flush=True)


if __name__ == "__main__":
    main()
