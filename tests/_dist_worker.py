"""Worker half of the two-process ``jax.distributed`` differential
(tests/test_distributed_2proc.py spawns two of these).

Each process contributes 4 virtual CPU devices; the combined 8-device
cluster mesh factors src=2 host-major, so the ``src`` axis is the only
one crossing the process (DCN) boundary — exactly the placement rule
``parallel/distributed.py`` documents.  Every process checks its
ADDRESSABLE shards of the sharded relay step bit-exactly against the
host oracle."""

import os
import sys

pid = int(sys.argv[1])
coord = sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import numpy as np  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

# gloo provides the cross-process CPU collectives
jax.config.update("jax_cpu_collectives_implementation", "gloo")

sys.path.insert(0, os.getcwd())
from easydarwin_tpu.parallel import (distributed, example_batch,  # noqa: E402
                                     sharded_relay_step)

DELAY = 73

# distributed.initialize MUST run before anything probes a backend —
# __graft_entry__ touches devices at import, which would latch a
# single-node CPU client and freeze process_count() at 1
assert distributed.init_from_env(coord, 2, pid)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8 and jax.local_device_count() == 4

from __graft_entry__ import _oracle_headers_kf  # noqa: E402

mesh = distributed.make_cluster_mesh(sub=2, win=2)
span = distributed.process_span(mesh)
assert span["num_processes"] == 2
assert not span["non_src_axis_crosses_hosts"], span
assert span["mesh_shape"] == {"src": 2, "sub": 2, "win": 2}

prefix, length, age, out_state, buckets = example_batch(
    n_src=2, n_sub=32, n_pkt=32)
age = (np.arange(32, dtype=np.int32)[::-1] * 9)[None, :].repeat(2, 0).copy()

specs = (P("src", "win", None), P("src", "win"), P("src", "win"),
         P("src", "sub", None), P("src", "sub"))
args = tuple(
    jax.make_array_from_callback(a.shape, NamedSharding(mesh, s),
                                 lambda idx, a=a: a[idx])
    for a, s in zip((prefix, length, age, out_state, buckets), specs))

step = sharded_relay_step(mesh, bucket_delay_ms=DELAY)
headers, mask, kf, total = jax.block_until_ready(step(*args))

oh, okf, oelig = _oracle_headers_kf(prefix, length, age, out_state,
                                    buckets, DELAY)
checked = 0
for arr, oracle in ((headers, oh), (kf, okf)):
    for shard in arr.addressable_shards:
        np.testing.assert_array_equal(np.asarray(shard.data),
                                      oracle[shard.index])
        checked += 1
assert checked >= 2
# newest-IDR pmax crosses win shards AND the answer replicates to every
# process identically (total is out_spec P(): fully replicated)
assert int(okf[0]) >= 32 // 2
assert total.is_fully_replicated
assert int(np.asarray(total)) == oelig
m_any = any(np.asarray(s.data).any() for s in mask.addressable_shards)
assert m_any
print(f"WORKER_OK {pid} shards={checked}", flush=True)
