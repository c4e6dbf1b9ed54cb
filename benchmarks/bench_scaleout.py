"""Figure 6 analog: scale-out of the decomposable TC plan over 1..8 workers.

Spawns subprocesses with forced host-device counts (the main process must
keep 1 device); they run on the host CPU by design, so they never contend
with a parent for a chip.  The measured quantity on CPU hosts is *structural*: the work
per worker shrinks with the shard count while the collective count stays at
one scalar psum per iteration — wall-clock speedup on a single physical core
is not expected, so the derived column reports per-worker row counts and the
collective census instead (that is what transfers to the real pod).
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

from .common import emit

SRC = Path(__file__).resolve().parents[1] / "src"

SCRIPT = """
import json, time
import numpy as np, jax, jax.numpy as jnp
import repro
from repro.core import distributed as D
from repro.roofline.hlo import parse_collectives
import functools

W = {W}
mesh = jax.make_mesh((W,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
rng = np.random.default_rng(0)
n = 256
adj = jnp.asarray(rng.random((n, n)) < 0.02)
fn = jax.jit(functools.partial(D.tc_decomposable, mesh))
lowered = fn.lower(jax.ShapeDtypeStruct((n, n), jnp.bool_))
st = parse_collectives(lowered.compile().as_text())
t0 = time.perf_counter()
out, it = fn(adj)
jax.block_until_ready(out)
dt = time.perf_counter() - t0
print(json.dumps({"workers": W, "rows_per_worker": n // W, "iters": int(it),
                  "collectives": st.op_counts, "wall_s": dt}))
"""


def main() -> list[str]:
    out = []
    for w in (1, 2, 4, 8):
        proc = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(SCRIPT.replace("{W}", str(w)))],
            capture_output=True, text=True, timeout=560,
            env={"XLA_FLAGS": f"--xla_force_host_platform_device_count={w}",
                 "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"})
        if proc.returncode != 0:
            raise RuntimeError(f"scale-out worker w={w} failed:\n"
                               f"{proc.stderr[-2000:]}")
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        out.append(emit(
            f"fig6_scaleout_w{w}", rec["wall_s"],
            f"rows_per_worker={rec['rows_per_worker']};iters={rec['iters']};"
            f"collectives={rec['collectives']}"))
    return out


if __name__ == "__main__":
    main()
