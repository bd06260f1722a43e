#!/usr/bin/env python3
"""sha256 of the lowered text of the step programs the benchmark's
configurations run, from shapes alone, on the CPU (nothing executes):

    python3 tools/lowering_hash.py [--root <another checkout>] > hashes.txt

A PR that says "no other configuration's program changed" runs this on its
parent (``git archive`` into a directory, ``--root`` it) and on its own
tree and diffs the two outputs: a line that is the same is a program that
lowers byte-equal. The pallas backend is interpreted on the CPU, so a
kernel's traced operations are in the text; the five layer patterns'
programs are listed last (ling, openpangu, lfm2, evabyte, laguna), for a PR that
changes them on purpose, and a line whose text holds the one-pass KDA
state update (``kda_step_inplace``: ling's pallas decode step), the
grouped expert matmul on a group's stack (``grouped_matmul_stacked``: the
pallas prefills of the three patterns with routed experts) or the flash
prefill of expanded latent attention (``mla_flash_prefill_attention``:
the pallas prefills of a pattern with latent layers from ``num_heads x T``
of 2**17, openpangu's from 1,024 positions, ling's from 4,096) says so in
further words. The two patterns with latent layers have, after their
three lines, one for every (rows, bucket) their cells warm
(``benchmark/run_helpers.warm_shapes``): ling 1 and 4 x 256 ... 2,048,
openpangu 1 and 4 x 1,024 ... 4,096.

    python3 tools/lowering_hash.py --v5e [--root <another checkout>]

lowers the Qwen step programs for a v5e that is DESCRIBED, not attached
(as ``tests/test_tpu_compile.py`` does): the 3B's on one chip, the 7B's
over the 2x2 mesh, the Pallas backend compiled, not interpreted, so the
text holds each Mosaic kernel as the chip gets it. The kernels are
serialised WITHOUT their source locations: with them a kernel that only
moved down its file hashes differently (PR 40: every 3B line differed
from the parent's until they were stripped, then none did).
"""

import argparse
import hashlib
import os
import sys
from functools import partial

ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ap.add_argument("--root", default=os.path.join(os.path.dirname(__file__), ".."),
                help="the checkout whose llmq_tpu is hashed (default: this one)")
ap.add_argument("--v5e", action="store_true",
                help="the Qwen programs lowered for a described v5e, Mosaic kernels in them")
args = ap.parse_args()
sys.path.insert(0, os.path.abspath(args.root))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from llmq_tpu.models.presets import get_preset  # noqa: E402
from llmq_tpu.models.transformer import build_model, init_params, make_kv_pages  # noqa: E402

S = jax.ShapeDtypeStruct
ROWS, PAGES, PLACES = 128, 1915, 64


def described_v5e():
    """The 3B's programs on one described chip and the 7B's over four."""
    import io

    from jax._src import tpu_custom_call
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from llmq_tpu.ops import dispatch
    from llmq_tpu.parallel.mesh import make_mesh
    from llmq_tpu.parallel.sharding import kv_page_pspec, param_shardings

    def without_locations(module, *, ir_version=None):
        # jax's ``_lower_mosaic_module_to_asm`` with ``strip-debuginfo`` first.
        flags = tpu_custom_call.tpu.private_has_communication(module.operation)
        with module.context as ctx, module.operation.location:
            op = module.operation.clone()
            was, ctx.allow_unregistered_dialects = ctx.allow_unregistered_dialects, True
            version = f"target-version={ir_version}" if ir_version is not None else ""
            try:
                tpu_custom_call.PassManager.parse(
                    "builtin.module(strip-debuginfo,mosaic-serde{serialize=true " + version + "})"
                ).run(op)
            finally:
                ctx.allow_unregistered_dialects = was
            buf = io.BytesIO()
            op.write_bytecode(buf, desired_version=0)
            return buf.getvalue(), tuple(flags)

    tpu_custom_call._lower_mosaic_module_to_asm = without_locations
    dispatch._interpret = lambda: False
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it cannot describe
        sys.exit(f"cannot describe a v5e:2x2 topology here: {exc}")
    for preset, tp in (("qwen2.5-3b", 1), ("qwen2.5-7b", 4)):
        cfg = get_preset(preset)
        mesh = make_mesh(tensor_parallel=tp, devices=topo.devices[:tp]) if tp > 1 else None
        whole = (
            NamedSharding(mesh, PartitionSpec()) if mesh is not None
            else SingleDeviceSharding(topo.devices[0])
        )
        model = build_model(cfg, mesh=mesh, attn_backend="pallas")
        shapes = jax.eval_shape(partial(init_params, cfg, dtype=jnp.bfloat16), jax.random.key(0))
        placed = (
            param_shardings(mesh, cfg, params=shapes) if mesh is not None
            else jax.tree.map(lambda _: whole, shapes)
        )
        params = jax.tree.map(lambda a, h: S(a.shape, a.dtype, sharding=h), shapes, placed)
        pool = NamedSharding(mesh, kv_page_pspec(cfg, tp)) if mesh is not None else whole
        kp, vp = jax.tree.map(
            lambda a: S(a.shape, a.dtype, sharding=pool),
            jax.eval_shape(lambda: make_kv_pages(cfg, PAGES, 128, jnp.bfloat16)),
        )
        s = partial(S, sharding=whole)
        texts = {
            "decode": jax.jit(model.decode, donate_argnums=(3, 4)).lower(
                params, s((ROWS,), jnp.int32), s((ROWS,), jnp.int32), kp, vp,
                s((ROWS, PLACES), jnp.int32), s((ROWS,), jnp.bool_)).as_text(),
        }
        for b, t in ((1, 512), (4, 2048)):
            texts[f"prefill_{b}x{t}"] = jax.jit(model.prefill, donate_argnums=(3, 4)).lower(
                params, s((b, t), jnp.int32), s((b,), jnp.int32), kp, vp,
                s((b, PLACES), jnp.int32)).as_text()
        for name, text in texts.items():
            print(preset, f"v5e-tp{tp}", name, hashlib.sha256(text.encode()).hexdigest()[:16],
                  len(text), "mosaic" if "tpu_custom_call" in text else "no-mosaic", flush=True)


if args.v5e:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp
    jax.config.update("jax_enable_compilation_cache", False)
    described_v5e()
    sys.exit(0)

#: The prefill buckets a cell warms, at 1 and 4 rows each, for the presets
#: whose latent layers take a kernel by the bucket's size.
WARMED = {
    "ling-3.0-flash-ep4": (256, 512, 1024, 2048),
    "openpangu-ultra-moe-718b-ep16": (1024, 2048, 4096),
}
KERNELS = ("kda_step_inplace", "grouped_matmul_stacked", "mla_flash_prefill_attention")

for preset in (
    "qwen2.5-3b", "qwen2.5-7b", "ling-3.0-flash-ep4", "openpangu-ultra-moe-718b-ep16",
    "lfm2-24b-a2b-pp5", "evabyte-6.5b-pp4", "laguna-s-2.1-ep4",
):
    cfg = get_preset(preset)
    hybrid = cfg.layer_pattern is not None
    for backend in ("xla", "pallas"):
        model = build_model(cfg, attn_backend=backend)
        params = jax.eval_shape(partial(init_params, cfg, dtype=jnp.bfloat16), jax.random.key(0))
        kw = dict(state_rows=ROWS + 1) if hybrid else {}
        kp, vp = jax.eval_shape(lambda: make_kv_pages(cfg, PAGES, 128, jnp.bfloat16, **kw))
        dec = partial(model.decode, counters=True, state_rows=1) if hybrid else model.decode
        texts = {
            "decode": jax.jit(dec).lower(
                params, S((ROWS,), jnp.int32), S((ROWS,), jnp.int32), kp, vp,
                S((ROWS, PLACES), jnp.int32), S((ROWS,), jnp.bool_)).as_text(),
        }
        shapes = [(1, 512), (4, 2048)]
        shapes += [(b, t) for t in WARMED.get(preset, ()) for b in (1, 4) if (b, t) not in shapes]
        for b, t in shapes:
            extra = (S((b,), jnp.int32),) if hybrid else ()
            texts[f"prefill_{b}x{t}"] = jax.jit(model.prefill).lower(
                params, S((b, t), jnp.int32), S((b,), jnp.int32), kp, vp,
                S((b, PLACES), jnp.int32), *extra).as_text()
        for name, text in texts.items():
            kernels = [k for k in KERNELS if k in text]
            print(preset, backend, name, hashlib.sha256(text.encode()).hexdigest()[:16],
                  len(text), *kernels, flush=True)
