"""The upper ends a language-model training cell's limits are set below, on
the chip, at the cell's own size, with the planted faults of the cell's OWN
reference: ``python3 -m benchmark.tools.calibrate_faults --workload <cell>
--seeds a,b [--faults] [--only name,...]``.

``calibrate_lm.py``'s readings (the plain reference recomputed with every
matrix product in fp8, the CONTROL, and in bfloat16, the witness) with the
faults of ``references/<the configuration's reference>.FAULTS`` in place of
``qwen3_next``'s, each put in the program's place against the plain
reference, the plain reference run once a seed.  One JSON line per seed and
reading, as each is done; nothing is compared with a limit here."""
from __future__ import annotations

import argparse
import importlib
import json
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--only", default="")
    a = ap.parse_args(argv)

    from .. import compare, harness
    from ..drivers import fit_lm
    from ..references import lowprec
    t0 = time.perf_counter()
    loaded = harness.load_cell(a.workload)
    harness.use_compile_cache()
    devices = harness.find_chip(int(loaded["cell"]["chips"]))
    cfg, mix = loaded["config"], loaded["traffic"]
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    stands_in = [("control_fp8", {"hooks": (lowprec.q_operand,
                                            lowprec.q_cotangent)}),
                 ("reference_bf16", {"hooks": (lowprec.bf16, lowprec.bf16)})]
    if a.faults:
        stands_in += [("fault_" + f, {"fault": f}) for f in ref.FAULTS]
    if a.only:
        stands_in = [s for s in stands_in if s[0] in a.only.split(",")]
    spec = {n: tuple(s)
            for n, s in ref.param_shapes(fit_lm.model_of(cfg)).items()}
    names = sorted(spec)
    for seed in (int(s) for s in a.seeds.split(",")):
        make_w0 = lambda: fit_lm.make_weights(seed, spec, cfg["init"])
        ring = fit_lm.lm_ring(mix, cfg["vocab_size"], seed)
        norms = lambda **kw: fit_lm.reference_norms(
            cfg, mix, devices, make_w0, *ring, names, **kw)
        true = norms()
        for name, how in stands_in:
            got = norms(**how)
            row, _ = compare.training_numbers(got[0], true[0], got[1],
                                              true[1], got[2], true[2])
            print(json.dumps(dict(row, reading=name, seed=seed,
                                  at_s=round(time.perf_counter() - t0, 1))),
                  flush=True)


if __name__ == "__main__":
    main()
