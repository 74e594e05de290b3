"""The upper ends a block-diffusion training cell's limits are set below, on
the chip, at the cell's own size: ``python3 -m benchmark.tools.calibrate_bd
--workload <cell> --seeds a,b [--faults]``.

``calibrate_lm.py``'s readings on this cell's ring (``drivers/fit_lm_bd``:
noised pairs, not next-token ids): for every seed the plain reference
recomputed with every matrix product in fp8 (the CONTROL) and, with
``--faults``, each of ``references/sdar.py``'s planted faults, all put in the
program's place against the plain reference (the program's own readings are
the witness that bfloat16 reads as correct).
The two faults of the loss's weight run as the unaltered reference on
altered labels, so they take no compile of their own.  One JSON line per
seed and reading, as each is done; nothing is compared with a limit here."""
from __future__ import annotations

import argparse
import json
import time


def readings(loaded, devices, seed, stands_in, report=None):
    """{name: the numbers compared} when the plain reference, altered as
    ``stands_in``'s ``(name, how)`` say, stands where the program stood:
    ``how`` holds keywords of ``fit_lm.reference_norms`` and, under
    ``relabel``, a function of the ring's labels.  ``report(name, row)`` is
    called as each reading is done."""
    import importlib
    from .. import compare
    from ..drivers import fit_lm, fit_lm_bd
    cfg, mix = loaded["config"], loaded["traffic"]
    model = fit_lm.model_of(cfg)
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    spec = {n: tuple(s) for n, s in ref.param_shapes(model).items()}
    names = sorted(spec)
    make_w0 = lambda: fit_lm.make_weights(seed, spec, cfg["init"])
    ring_x, ring_y = fit_lm_bd.bd_ring(mix, model, seed)
    gap = fit_lm_bd.noise_gap(mix, model, seed, ring_x, ring_y, ref)
    norms = lambda y, **kw: fit_lm.reference_norms(
        cfg, mix, devices, make_w0, ring_x, y, names, **kw)
    true = norms(ring_y)
    row = {}
    for name, how in stands_in:
        how = dict(how)
        relabel = how.pop("relabel", None)
        got = norms(ring_y if relabel is None else relabel(ring_y), **how)
        row[name], _ = compare.training_numbers(
            got[0], true[0], got[1], true[1], got[2], true[2])
        row[name]["noise_gap"] = gap
        if report is not None:
            report(name, row[name])
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", action="store_true")
    a = ap.parse_args(argv)

    from .. import harness
    from ..references import lowprec, sdar
    t0 = time.perf_counter()
    loaded = harness.load_cell(a.workload)
    harness.use_compile_cache()
    devices = harness.find_chip(int(loaded["cell"]["chips"]))
    # the label faults first: they reuse the plain reference's executable
    stands_in = [("fault_" + f, {"relabel": sdar.WEIGHT_FAULTS[f]})
                 for f in sdar.WEIGHT_FAULTS] if a.faults else []
    stands_in.append(("control_fp8", {"hooks": (lowprec.q_operand,
                                                lowprec.q_cotangent)}))
    if a.faults:
        stands_in += [("fault_" + f, {"fault": f}) for f in sdar.FAULTS
                      if f not in sdar.WEIGHT_FAULTS]
    for seed in (int(s) for s in a.seeds.split(",")):
        readings(loaded, devices, seed, stands_in, lambda name, row: print(
            json.dumps(dict(row, reading=name, seed=seed,
                            at_s=round(time.perf_counter() - t0, 1))),
            flush=True))


if __name__ == "__main__":
    main()
