"""Share of its roofline that the 1-D transform stages reach: the least
time their work needs on this chip over ``transforms_ms``.  The least time
is the larger of the bytes the stages must move over the HBM peak (each
stage reads its input and writes its output once) and the operations they
must do over the peak rate (5 N log2 N per complex transform of length N,
half that for a real one), both from the configuration's shapes
(``bench/work.py``) under the cheapest valid execution order, split evenly
over the chips."""
import harness
import reduce
import work


def read(run):
    t_ms = reduce.per_step_ms(run.trace, reduce.in_stage(("fwd", "bwd")))
    if not t_ms or not run.peaks:
        return None
    fields = run.cell.mix.get("fields_per_step", 1)
    nbytes, flops = work.least_transform_work(run.config, fields)
    chips = run.mesh[0] * run.mesh[1]
    t_bytes = nbytes / chips / run.peaks["hbm_bytes_per_s"]
    t_flops = flops / chips / run.peaks["flops_per_s"]
    bound = "HBM bytes" if t_bytes >= t_flops else "operations"
    harness.log(f"transforms roofline: {nbytes / chips:.6e} B and "
                f"{flops / chips:.6e} flop per chip per step; least time "
                f"{max(t_bytes, t_flops) * 1e3:.6f} ms, bound by {bound}; "
                f"measured {t_ms:.6f} ms")
    return 100.0 * max(t_bytes, t_flops) * 1e3 / t_ms
