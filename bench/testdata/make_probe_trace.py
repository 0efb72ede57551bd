"""Records ``probe.xplane.pb``: a small profiler trace, on a TPU, of a
Pallas kernel (flash prefill) inside a program named like the engine's
admission prefill (``jit__prefill_fn``) and a plain program named like its
decode window (``jit_counted_fn``), three times each; and dumps its
planes, lines, event names and stats to ``<dir>/planes.json``.

    python3 bench/testdata/make_probe_trace.py <dir>
"""
import glob, json, os, shutil, sys, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
import jax, jax.numpy as jnp
from repro.kernels.flash_prefill import flash_prefill_attention

def _prefill_fn(q, k, v):
    o = flash_prefill_attention(q, k, v, interpret=False)
    return (o.astype(jnp.float32) * 2).sum()

def counted_fn(x):
    return jnp.tanh(x @ x).sum()

key = jax.random.PRNGKey(0)
q = jax.random.normal(key, (1, 512, 8, 128), jnp.bfloat16)
k = jax.random.normal(key, (1, 512, 2, 128), jnp.bfloat16)
f = jax.jit(_prefill_fn); g = jax.jit(counted_fn)
x = jnp.ones((1024, 1024), jnp.bfloat16)
f(q, k, k).block_until_ready(); g(x).block_until_ready()
d = sys.argv[1] if len(sys.argv) > 1 else "trace_probe"
shutil.rmtree(d, ignore_errors=True)
jax.profiler.start_trace(d)
t0 = time.perf_counter_ns()
for i in range(3):
    with jax.profiler.TraceAnnotation("step"):
        a = f(q, k, k); b = g(x)
    time.sleep(0.01)
    with jax.profiler.TraceAnnotation("submit"):
        jax.block_until_ready((a, b))
jax.profiler.stop_trace()
path = glob.glob(d + "/**/*.xplane.pb", recursive=True)[0]
print("xplane", path, os.path.getsize(path), "host t0 perf_ns", t0)
from jax.profiler import ProfileData
pd = ProfileData.from_file(path)
out = []
for p in pd.planes:
    lines = []
    for l in p.lines:
        evs = list(l.events)
        lines.append({"line": l.name, "n": len(evs), "events": [
            {"name": e.name, "start_ns": e.start_ns, "dur_ns": e.duration_ns,
             "stats": {k2: str(v2) for k2, v2 in e.stats}} for e in evs[:12]]})
    out.append({"plane": p.name, "lines": lines,
                "stats": {k2: str(v2) for k2, v2 in getattr(p, "stats", [])}})
json.dump(out, open(os.path.join(d, "planes.json"), "w"), indent=1)
for p in out:
    print("PLANE", p["plane"], [(l["line"], l["n"]) for l in p["lines"]])
