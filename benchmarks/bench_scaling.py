"""Paper Fig. 6 (weak scaling) / Fig. 7 (strong-scaling proxy): domain
decomposition vs pipeline parallelism.

This container's "devices" share one CPU's cores, so wall-clock scaling is
not measurable; instead (per the assignment's dry-run methodology) we lower
both schedules at production scale for P in {2,4,8}, parse per-device FLOPs
and collective wire bytes from the compiled HLO, and project parallel
efficiency under TWO hardware models:

  * A100/NVLink (19.5 TF f32, 600 GB/s) — the paper's testbed. This
    REPRODUCES Fig. 6's contrast (DD > 0.9, PP bubble-bound <= 0.5).
  * TPU v5e/ICI (197 TF bf16, 50 GB/s/link) — our target. The same comm
    volumes are strongly bound by ICI, which motivates the beyond-paper
    comm optimizations in EXPERIMENTS §Perf.

  eff_DD(P) = t_compute / (t_compute + t_comm)
  eff_PP(P) = bubble(M,P) x t_compute / (t_compute + t_comm)
"""
from __future__ import annotations

import textwrap

from benchmarks.cpu_child import run_cpu_script
from repro.common.constants import TARGET_DEVICE_KIND, chip_peaks

_V5E = chip_peaks(TARGET_DEVICE_KIND)
PEAK_FLOPS_BF16 = _V5E.flops_bf16
ICI_BANDWIDTH_PER_LINK = _V5E.ici_bandwidth_per_link

A100_PEAK_F32 = 19.5e12
NVLINK_BW = 600e9


def _pencil_shape(p: int) -> tuple:
    """Near-square (px, py) factorization with px*py == p."""
    px = 1
    for cand in range(int(p ** 0.5), 0, -1):
        if p % cand == 0:
            px = p // cand
            break
    return px, p // px


def _measure(p: int, mode: str, nx: int | None = None):
    """Lower DD (1-D x-decomposition), DD2D (pencil) or PP FNO fwd at P
    shards (weak scaling: nx = 32*P unless a fixed nx is given for strong
    scaling), production width/modes; return per-device flops + collective
    bytes."""
    px, py = _pencil_shape(p)
    script = textwrap.dedent(
        """
        import json
        import jax, jax.numpy as jnp
        from repro.core import FNOConfig, forward_and_specs, init_params, make_pipeline_forward
        from repro.core.partition import make_mesh
        from repro.launch import hlo_analysis as ha

        P = %d
        PX, PY = %d, %d
        mode = %r
        nx = %d if %d else 32 * P
        cfg = FNOConfig(grid=(nx, 128, 128, 64), modes=(16, 16, 16, 8),
                        width=40, n_blocks=P if mode == "pp" else 4,
                        decoder_dim=128)
        params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
        x = jax.ShapeDtypeStruct((2, 1, nx, 128, 128, 64), jnp.float32)
        if mode == "dd":
            mesh = make_mesh((1, P), ("data", "model"))
            fwd, _, _ = forward_and_specs(mesh, cfg, dp_axes=("data",), model_axis="model")
        elif mode == "dd2d":
            mesh = make_mesh((1, PX, PY), ("data", "mx", "my"))
            fwd, _, _ = forward_and_specs(mesh, cfg, dp_axes=("data",),
                                          model_axis=("mx", "my"))
        else:
            mesh = make_mesh((1, P), ("data", "model"))
            fwd = make_pipeline_forward(mesh, cfg, n_micro=2)
        hlo = jax.jit(fwd).lower(params, x).compile().as_text()
        comp = ha.collect_compute(hlo)
        coll = ha.collect_collectives(hlo, P)
        print("RESULT" + json.dumps({
            "flops": comp["flops"], "coll_bytes": coll.total_bytes,
            "by_kind": coll.bytes_by_kind,
        }))
        """
    ) % (p, px, py, mode, nx or 0, nx or 0)
    return run_cpu_script(script, n_devices=max(p, 1), timeout=1800)


def _eff(flops, coll, peak, bw, bubble=1.0):
    t_comp = flops / peak
    t_comm = coll / bw
    return bubble * t_comp / (t_comp + t_comm)


def run():
    rows = []
    for p in (2, 4, 8):
        dd = _measure(p, "dd")
        pp = _measure(p, "pp")
        dd2d = _measure(p, "dd2d") if p >= 4 else None
        bubble = 2 / (2 + p - 1)  # M=2 microbatches (paper's BS=2 case)
        row = {
            "P": p,
            "a100_dd": round(_eff(dd["flops"], dd["coll_bytes"], A100_PEAK_F32, NVLINK_BW), 3),
            "a100_pp": round(_eff(pp["flops"], pp["coll_bytes"], A100_PEAK_F32, NVLINK_BW, bubble), 3),
            "v5e_dd": round(_eff(dd["flops"], dd["coll_bytes"], PEAK_FLOPS_BF16, ICI_BANDWIDTH_PER_LINK), 3),
            "v5e_pp": round(_eff(pp["flops"], pp["coll_bytes"], PEAK_FLOPS_BF16, ICI_BANDWIDTH_PER_LINK, bubble), 3),
            "dd_coll_bytes": dd["coll_bytes"],
            "pp_coll_bytes": pp["coll_bytes"],
        }
        if dd2d is not None:
            # 1-D vs 2-D: same flops (the pencil splits the SAME transform
            # over a (px, py) grid of devices) but two smaller all-to-alls,
            # and crucially no nx/2mx parallelism cap.
            row["a100_dd2d"] = round(
                _eff(dd2d["flops"], dd2d["coll_bytes"], A100_PEAK_F32, NVLINK_BW), 3)
            row["v5e_dd2d"] = round(
                _eff(dd2d["flops"], dd2d["coll_bytes"], PEAK_FLOPS_BF16, ICI_BANDWIDTH_PER_LINK), 3)
            row["dd2d_coll_bytes"] = dd2d["coll_bytes"]
            row["dd2d_mesh"] = list(_pencil_shape(p))
        rows.append(row)
    derived = {
        f"weak_P{r['P']}": {
            k: r[k]
            for k in (
                "a100_dd", "a100_pp", "v5e_dd", "v5e_pp",
                "a100_dd2d", "v5e_dd2d", "dd_coll_bytes", "dd2d_coll_bytes",
            )
            if k in r
        }
        for r in rows
    }
    # Fig. 7: strong scaling — fixed 128^3 x 64 grid, per-device work shrinks
    base = _measure(1, "dd", nx=128)
    t1 = base["flops"] / A100_PEAK_F32
    for p in (2, 4, 8):
        dd = _measure(p, "dd", nx=128)
        tp = dd["flops"] / A100_PEAK_F32 + dd["coll_bytes"] / NVLINK_BW
        derived[f"strong_P{p}_a100_dd_speedup"] = round(t1 / tp, 2)
    derived["paper_claim"] = "A100: weak DD >0.90, PP <=0.50 (Fig. 6); strong DD near-linear (Fig. 7)"
    derived["note"] = "v5e columns motivate §Perf comm optimizations"
    derived["dd2d_note"] = (
        "dd2d = 2-D pencil decomposition (BEYOND-PAPER): lifts the 1-D cap "
        "of nx/2mx devices to (nx/2mx)*(ny/2my); compare dd vs dd2d "
        "coll_bytes at equal P for the comm cost of the second all-to-all"
    )
    return 0.0, derived
