"""The serving readers' kernel signatures against the program's own decode
step, compiled for a described TPU v5e chip (no chip attached): every
Pallas kernel of the step is claimed by exactly one of the two readers, the
column-wise linear's by ``linear_roofline.serve`` and the paged
attention's by ``paged_attn_roofline.serve``.  The same signature for the
column-wise linear also claims the ResNet head's linear in the trace
recorded on the chip, whose operand list is printed the same way.

The step is compiled in a child process: describing the chip loads the TPU
library, which admits one loader per process, and dispatch ranks the
Pallas candidates first only where the backend reads as a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

from chipbench import harness, readers
from chipbench import trace_reduce as tr

ROOT = Path(__file__).resolve().parents[2]
METRICS = ROOT / "chipbench" / "metrics"

CHILD = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from chipbench.drivers import serve_paged
from chipbench.tests import tiny
from repro.models import registry as reg
from repro.serve.engine import _phased

dev = SingleDeviceSharding(topologies.get_topology_desc(
    platform="tpu", topology_name="v5e:2x2").devices[0])
jax.config.update("jax_enable_compilation_cache", False)
jax.default_backend = lambda: "tpu"  # dispatch's heuristic, as on the chip
cfg = tiny.load("configs/qwen2-0.5b-c50.json")
cfg["num_hidden_layers"] = 2  # published widths; the layers share one body
pcfg = serve_paged.program_config(cfg)
params, _ = reg.abstract_params(pcfg)
slots, n_max, ps = 64, 48, 8  # the gen256 cell: 64 slots, 384 rows of 8
cache = jax.eval_shape(reg.paged_cache_init_fn(pcfg, slots * n_max, ps))


def on_chip(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev), tree)


args = (on_chip(params), on_chip(cache),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=dev),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=dev),
        jax.ShapeDtypeStruct((slots, n_max), jnp.int32, sharding=dev))
step = jax.jit(_phased(reg.paged_decode_fn(pcfg, ps), "decode"))
text = step.lower(*args).compile().as_text()
print(json.dumps([ln.strip() for ln in text.splitlines()
                  if 'custom_call_target="tpu_custom_call"' in ln]))
"""


def _reader(name):
    return harness.load_module(METRICS / f"{name}.py")


def _decode_step_kernels():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPRO_DISPATCH_DB=os.devnull, REPRO_DISPATCH_PROFILE="0")
    out = subprocess.run(
        [sys.executable, "-c", CHILD.format(root=str(ROOT),
                                            src=str(ROOT / "src"))],
        capture_output=True, text=True, env=env, timeout=600, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_serve_signatures_claim_the_decode_step_kernels():
    texts = _decode_step_kernels()
    ops = [tr.Op(0, t.split(" = ", 1)[0], "custom-call", t, 0.0, 1.0)
           for t in texts]
    red = tr.Reduced(ops=ops, modules=[], annotations=[], n_devices=1)
    lin = _reader("linear_roofline.serve")
    pag = _reader("paged_attn_roofline.serve")
    claims = {"linear": lin.claims, "paged": pag.claims}
    lines = readers.claim_report(red, claims, set(claims))
    assert not [ln for ln in lines if "claimed by none" in ln], lines
    # q, o, gate, up and down are compressed; k and v stay dense (XLA)
    assert sum(map(lin.claims, ops)) == 5
    assert sum(map(pag.claims, ops)) == 1


def test_linear_signature_on_the_recorded_trace():
    red = tr.reduce_file(str(ROOT / "chipbench" / "tests" / "data" /
                             "resnet18_b32_v5e.xplane.pb"))
    lin = _reader("linear_roofline.serve")
    claimed = {o.name.split(".")[0] for o in red.ops if lin.claims(o)}
    assert claimed == {"%_lambda_"}  # the head: x, idx, values
