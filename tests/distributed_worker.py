"""Worker script for the two-process jax.distributed test: joined by
tests/test_distributed.py as two real OS processes, each with 4 virtual
CPU devices, forming one 8-device global mesh spanning processes.

Runs the sharded trim across the process-spanning mesh on a graph whose
edges are split between the processes, and checks the replicated result
against the known answer. Prints DIST-OK on success (the parent asserts
it). Run directly:

    python tests/distributed_worker.py <process_id> <num_processes> <port>
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

proc_id, n_procs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jepsen_tpu.parallel import distributed as dist  # noqa: E402

dist.initialize(f"127.0.0.1:{port}", n_procs, proc_id, local_devices=4)

import jax  # noqa: E402

assert jax.process_count() == n_procs, jax.process_count()
assert jax.device_count() == 4 * n_procs, jax.device_count()
assert jax.local_device_count() == 4, jax.local_device_count()

mesh = dist.global_mesh()

# global graph over 8 nodes: 0->1->2->0 (cycle) plus chains 3->4->5, 6->7.
# process 0 holds the cycle's edges, process 1 the acyclic tails — the
# verdict needs BOTH shards' degrees, so a psum that failed to cross
# processes would get it wrong.
if proc_id == 0:
    local_src = [0, 1, 2, 3]
    local_dst = [1, 2, 0, 4]
else:
    local_src = [4, 6, 7]
    local_dst = [5, 7, 6]

mask = dist.trim_to_cycles_distributed(8, local_src, local_dst, mesh)
expected = [True, True, True, False, False, False, True, True]
assert mask.tolist() == expected, mask.tolist()

# batch_check across processes: keys split between hosts, verdicts
# allgathered — every process must see the full result list, including
# the one injected invalid key
from jepsen_tpu.checker.linear_encode import encode_register_ops  # noqa: E402


def _reg_history(writes, bad_read=None):
    h = []
    for i, v in enumerate(writes):
        h.append({"type": "invoke", "process": 0, "f": "write", "value": v})
        h.append({"type": "ok", "process": 0, "f": "write", "value": v})
    if bad_read is not None:
        h.append({"type": "invoke", "process": 1, "f": "read", "value": None})
        h.append({"type": "ok", "process": 1, "f": "read", "value": bad_read})
    return h


streams = [encode_register_ops(_reg_history([1, 2, 3])) for _ in range(7)]
streams.append(encode_register_ops(_reg_history([1, 2, 3], bad_read=99)))
results = dist.batch_check_distributed(streams)
assert len(results) == 8
assert all(r[0] for r in results[:7]), results
assert results[7][0] is False, results[7]

print(f"DIST-OK {proc_id}", flush=True)
