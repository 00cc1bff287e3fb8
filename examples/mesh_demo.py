"""Multi-chip engine — the deployable cluster unit: one engine sharded
over an 8-device mesh, budgets conserved across chips with ICI
collectives instead of a token-server RPC (the TPU-native replacement
for sentinel-demo-cluster's server deployment).

With JAX_PLATFORMS=cpu it runs on a virtual 8-device CPU mesh; on a TPU
it needs 8 chips.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import _bootstrap  # noqa: F401

import sentinel_tpu as st

eng = st.get_engine()
eng.enable_mesh(8)
st.flow_rule_manager.load_rules([st.FlowRule("global-api", count=20)])

now = eng.clock.now_ms()
ops = eng.submit_many([{"resource": "global-api", "ts": now} for _ in range(128)])
eng.flush()
admitted = sum(op.verdict.admitted for op in ops)
print(f"128 entries sharded over 8 devices against count=20:")
print(f"  admitted {admitted} (exactly the global budget, not 8 x 20)")
stats = eng.cluster_node_stats("global-api")
print(f"  minute totals: pass={stats['total_pass_minute']}  "
      f"block={stats['total_block_minute']}")
