"""Paper Fig. 8c analogue: machine scalability.

The bench runs on forced CPU host devices, so it measures no chip
speedup. It reports the two halves a CPU run can show:

  (a) measured: the distributed engine at P = 1..8 parts on fake host
      devices — per-part WORK (edges + vertices processed) must drop as
      1/P while results stay identical (the scaling *mechanism*);
  (b) modeled: speedup = T1 / max(T1/P, wire(P)/link_bw), where wire(P)
      is the MEASURED per-superstep exchange payload the run reports in
      info["bytes_exchanged"] (the same accounting the wire codecs
      shrink), so the model shows what exchange compression buys at each
      P: the exact and q8ef columns share T1/P and differ only in the
      wire term (EXPERIMENTS §Roofline).
"""
import json
import subprocess
import sys

from .common import row

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, time
import numpy as np
import jax
from jax.sharding import Mesh
import repro
from repro.core import io as gio
from repro.core.engines.distributed import (build_sharded_graph,
                                            run_vcprog_distributed)
from repro.core.operators import PageRankProgram

g = gio.lognormal_graph(4000, mu=1.6, sigma=1.1, seed=8)
ref, _ = repro.UniGPS().pagerank(g, num_iters=10, engine="pushpull")
out = []
for P in (1, 2, 4, 8):
    dev = np.asarray(jax.devices()[:P])
    mesh = Mesh(dev, ("graph",))
    sg = build_sharded_graph(g, P)
    t0 = time.time()
    vp, info = run_vcprog_distributed(PageRankProgram(g.num_vertices, 10),
                                      g, max_iter=10, mesh=mesh,
                                      schedule="ring", frontier="sparse")
    dt = time.time() - t0
    err = float(np.abs(vp["rank"] - ref).max())
    work = int(sg["edge_mask"].sum(axis=(1, 2)).max())  # max edges/part
    bts = info["bytes_exchanged"]
    out.append(dict(P=P, seconds=dt, max_edges_per_part=work, err=err,
                    wire_exact=bts["sparse_per_superstep"]["exact"],
                    wire_q8ef=bts["sparse_per_superstep"]["q8ef"]))
print("RESULT:" + json.dumps(out))
"""


def main():
    import jax
    from repro.envutil import subprocess_env
    if jax.default_backend() != "cpu":
        # the child runs on forced CPU host devices; a parent on an
        # accelerator holds the chip, and the model below is a CPU one
        raise RuntimeError("fig8c is a CPU-only bench (forced host "
                           f"devices); this process runs on "
                           f"{jax.default_backend()!r}")
    r = subprocess.run([sys.executable, "-c", _SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env=subprocess_env(JAX_PLATFORMS="cpu"))
    if r.returncode != 0:
        raise RuntimeError(f"fig8c child failed:\n{r.stderr[-2000:]}")
    data = json.loads([l for l in r.stdout.splitlines()
                       if l.startswith("RESULT:")][0][7:])
    from repro.launch.roofline import peaks_for
    LINK_BW = peaks_for("TPU v5 lite").ici_bw
    e1 = data[0]["max_edges_per_part"]
    t1, iters = data[0]["seconds"], 10
    for d in data:
        assert d["err"] < 1e-6
        # modeled wall per run: perfect-compute 1/P scaling vs the wire
        # term built from the MEASURED per-superstep exchange payload
        model = {k: t1 / max(t1 / d["P"],
                             iters * d[f"wire_{k}"] / LINK_BW)
                 for k in ("exact", "q8ef")}
        row(f"fig8c.ring.P{d['P']}", d["seconds"],
            f"max_edges_per_part={d['max_edges_per_part']};"
            f"work_scaling={e1/d['max_edges_per_part']:.2f}x;"
            f"wire_exact_B={d['wire_exact']};"
            f"wire_q8ef_B={d['wire_q8ef']};"
            f"modeled_speedup_exact={model['exact']:.2f}x;"
            f"modeled_speedup_q8ef={model['q8ef']:.2f}x")


if __name__ == "__main__":
    main()
