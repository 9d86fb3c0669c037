#!/usr/bin/env bash
# Local CI: tier-1 test suite + quick benchmark smoke (catches dispatch
# latency/selection regressions before they land).  Usage: scripts/ci.sh
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== contract lints (Pallas/dispatch/registry static checks) =="
python -m repro.analysis src

echo "== tier-1 tests =="
python -m pytest -x -q

echo "== serve scheduler smoke =="
python -m repro.launch.serve --arch smollm-360m --smoke --continuous \
    --requests 6 --slots 3 --prompt-len 12 --new-tokens 8 --prefill-chunk 8

echo "== paged-KV scheduler smoke (packed prefill + paged decode, trace validated) =="
PAGED_TRACE="$(mktemp -t repro_paged_XXXXXX.json)"
trap 'rm -f "$PAGED_TRACE"' EXIT
python -m repro.launch.serve --arch smollm-360m --smoke --continuous \
    --paged --page-size 8 --requests 6 --slots 3 --prompt-len 12 \
    --new-tokens 8 --prefill-chunk 8 --trace "$PAGED_TRACE"
python -m repro.obs.validate "$PAGED_TRACE"

echo "== obs trace smoke (serve --trace -> Perfetto-loadable JSON) =="
OBS_TRACE="$(mktemp -t repro_obs_XXXXXX.json)"
trap 'rm -f "$OBS_TRACE" "$PAGED_TRACE"' EXIT
python -m repro.launch.serve --arch smollm-360m --smoke --continuous \
    --requests 6 --slots 3 --prompt-len 12 --new-tokens 8 --prefill-chunk 8 \
    --trace "$OBS_TRACE"
# validator: non-empty, per-lane monotone timestamps, balanced B/E nesting
python -m repro.obs.validate "$OBS_TRACE"

echo "== chaos smoke (seeded faults: quarantine-degradation + request lifecycle) =="
CHAOS_TRACE="$(mktemp -t repro_chaos_XXXXXX.json)"
trap 'rm -f "$CHAOS_TRACE" "$OBS_TRACE" "$PAGED_TRACE"' EXIT
python scripts/chaos_smoke.py --trace "$CHAOS_TRACE"
python -m repro.obs.validate "$CHAOS_TRACE"

echo "== sparse finetune smoke (conv VJP backward, interpret mode) =="
python -c "from repro.models.vision import train_smoke; train_smoke(steps=2)"

echo "== train chaos smoke (kill -> restart -> bitwise-identical resume) =="
python scripts/train_chaos_smoke.py

echo "== quick benchmarks =="
python -m benchmarks.run --quick

echo "== conv megakernel smoke (writes BENCH_conv.json) =="
python -m benchmarks.bench_conv_fused --quick --json

echo "== banded conv smoke (forced double-buffered DMA path) =="
REPRO_DISPATCH_FORCE=fused_banded_pallas python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro.core import SparsityConfig, conv_init, conv_apply, unbox_tree
cfg = SparsityConfig(sparsity=0.5, m=None, tile=8, min_dim=8,
                     format="compressed_pallas")
params, _ = unbox_tree(conv_init(jax.random.PRNGKey(0), 8, 16, 3, 3, cfg))
x = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 10, 10))
y = conv_apply(params, x, kh=3, kw=3, stride=1, pad=1)      # forced banded
y_ref = conv_apply(params, x, kh=3, kw=3, stride=1, pad=1,
                   impl="im2col_sparse_xla")
np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                           rtol=1e-4, atol=1e-4)
print("banded DMA smoke OK:", y.shape)
PY
