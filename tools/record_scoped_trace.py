#!/usr/bin/env python3
"""Record ``benchmarks/testdata/tpu_v5e_scopes.xplane.pb``: a device trace
of a tiny scoped train step, for the tests of ``benchmarks/lib/scopes.py``.

    chiprun -- python3 tools/record_scoped_trace.py

One decoder layer of ``models/llama.py`` (d_model 256, T = 1024, selective
remat, so ``value_and_grad`` + ``jax.checkpoint`` + the blockwise flash
kernels) under adamw through ``parallel.train.make_multistep`` — the
benchmark's own step program ``jit(_run)``, two steps a call — run three
times under the profiler after a warm-up call.  The file, cut to what the
readers read (:func:`trim`), goes to ``chiprun_out/tpu_v5e_scopes.xplane.pb``
and the scope table is printed.  ``<in> <out>`` as arguments cut a file
recorded earlier, on any machine.
Needs a TPU (``REHEARSE=1`` runs the control flow on the CPU, whose trace
holds no device plane).
"""
import glob, os, sys, tempfile, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.lib import scopes, tracered

#: What the tests read of an op's metadata; source stacks and layouts, which
#: they do not, are most of a file's bytes.
KEPT_STATS = ("tf_op", "flops", "bytes_accessed", "hlo_category")
KEPT_LINES = (tracered.MODULES_LINE, tracered.OPS_LINE)


_put = scopes.put


def trim(buf):
    """The recorded XSpace cut to what the repo's readers read, every kept
    field byte for byte as the profiler wrote it: the ``/device:TPU:<n>``
    planes; of their lines ``XLA Modules`` and ``XLA Ops``, each event's
    metadata id, offset and duration; of the op metadata id, name and the
    :data:`KEPT_STATS`; the stat names; the plane's own stats (the peaks)."""
    F = scopes.fields
    out = b""
    for field, wire, plane in F(buf, 0, len(buf)):
        if field != 1 or wire != 2:
            continue
        parts = [(g, w, v) for g, w, v in F(buf, *plane)]
        name = next(scopes._text(buf, v) for g, w, v in parts if g == 2)
        if not tracered.DEVICE_PLANE.match(name):
            continue
        stat_ids = {}
        for g, w, v in parts:
            if g == 5:
                md = next(x for h, _, x in F(buf, *v) if h == 2)
                sid = next(x for h, _, x in F(buf, *md) if h == 1)
                stat_ids[sid] = scopes._text(
                    buf, next(x for h, _, x in F(buf, *md) if h == 2))
        body = b""
        for g, w, v in parts:
            if g == 3:  # a line
                sub = [(h, x) for h, _, x in F(buf, *v)]
                if scopes._text(buf, next(x for h, x in sub if h == 2)) not in KEPT_LINES:
                    continue
                line = b""
                for h, x in sub:
                    if h == 4:  # an event, without its per-event stats
                        x = b"".join(
                            _put(k, y) for k, _, y in F(buf, *x) if k in (1, 2, 3))
                        line += _put(4, x)
                    else:
                        line += _put(h, x if isinstance(x, int) else buf[x[0]:x[1]])
                body += _put(3, line)
            elif g == 4:  # an event_metadata entry
                key = next(x for h, _, x in F(buf, *v) if h == 1)
                md = next(x for h, _, x in F(buf, *v) if h == 2)
                kept_md = b""
                for h, _, x in F(buf, *md):
                    if h == 1:
                        kept_md += _put(1, x)
                    elif h == 2:
                        kept_md += _put(2, buf[x[0]:x[1]])
                    elif h == 5:
                        sid = next(y for k, _, y in F(buf, *x) if k == 1)
                        if stat_ids.get(sid) in KEPT_STATS:
                            kept_md += _put(5, buf[x[0]:x[1]])
                body += _put(4, _put(1, key) + _put(2, kept_md))
            elif w == 2:
                body += _put(g, buf[v[0]:v[1]])
            else:
                body += _put(g, v)
        out += _put(1, body)
    return out


if len(sys.argv) == 3:  # trim a file recorded earlier: <in> <out>
    with open(sys.argv[1], "rb") as f:
        cut = trim(f.read())
    with open(sys.argv[2], "wb") as f:
        f.write(cut)
    print("bytes", os.path.getsize(sys.argv[1]), "->", len(cut))
    sys.exit(0)

from ddl_tpu.bringup import bring_up
REH = bool(os.environ.get("REHEARSE"))
bring_up("cpu" if REH else None)
import jax, jax.numpy as jnp, numpy as np, optax
from jax.sharding import Mesh, PartitionSpec as P
from ddl_tpu.models import llama
from ddl_tpu.parallel.train import make_multistep

T = 128 if REH else 1024
cfg = llama.LlamaConfig(
    vocab=512, d_model=256, n_layers=1, n_heads=2, n_kv_heads=1, d_ff=512,
    max_seq=T, rope_theta=1e6, param_dtype=jnp.bfloat16, remat="selective",
    attn_impl="flash",
)
mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
init, multi = make_multistep(
    lambda p, b: llama.next_token_loss(p, b[0], cfg), optax.adamw(3e-4), mesh,
    llama.param_specs(cfg), batch_spec=P(("dp",)), n_steps=2,
)
state = init(llama.init_params(cfg, jax.random.key(0)))
tokens = jax.random.randint(jax.random.key(1), (2, 2, T), 0, cfg.vocab)
state, losses = multi(state, (tokens,), per_step=True)  # compiles
losses.block_until_ready()
out = tempfile.mkdtemp(prefix="scoped_trace_")
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 1
jax.profiler.start_trace(out, profiler_options=opts)
for _ in range(3):
    with jax.profiler.TraceAnnotation("bench.window_hook"):
        state, losses = multi(state, (tokens,), per_step=True)
    losses.block_until_ready()
    time.sleep(0.005)
jax.profiler.stop_trace()
files = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
os.makedirs("chiprun_out", exist_ok=True)
kept = os.path.join(out if REH else "chiprun_out", "tpu_v5e_scopes.xplane.pb")
with open(files[0], "rb") as f:
    whole = f.read()
with open(kept, "wb") as f:
    f.write(whole if REH else trim(whole))
print("losses", np.asarray(losses), "bytes", len(whole), "->", os.path.getsize(kept))
if not REH:
    print(scopes.render(scopes.tabulate(scopes.read_planes(kept))))
