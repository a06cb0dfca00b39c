"""The benchmark's cells cut to a size the CPU runs in seconds: widths
shrunk to 256, the sites, three local steps (all recorded), the routing
geometry (groups of 64 tokens, capacity 20, top-8 of 32 experts), the
wire codec and the device layout kept (a four-chip cell on four virtual
CPU devices, ``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
At this size the sound program stays inside the cells' chip-calibrated
limits and the float8 control does not."""
import copy

import run

LOCAL_STEPS = 3


def cell(workload: str) -> dict:
    c = copy.deepcopy(run.load_cell(workload))
    cfg, mix = c["config"], c["traffic"]
    cfg.update(hidden_size=256, num_attention_heads=8, num_key_value_heads=4,
               head_dim=32, vocab_size=1024)
    rep = cfg["program"].setdefault("replace", {})
    rep.update(d_model=256, num_heads=8, num_kv_heads=4)
    mix.update(seq_len=64, local_steps=LOCAL_STEPS)
    if cfg.get("num_local_experts"):
        from repro.config import MoEConfig

        cfg.update(intermediate_size=64)
        cfg["departures"]["attention_multiplier"]["runs"] = 32 ** -0.5
        rep["moe"] = MoEConfig(num_experts=32, experts_per_token=8, d_ff=64,
                               capacity_factor=1.25)
        # 8 x 64 tokens: the program's rule makes 8 groups of 64, each
        # expert taking int(64 * 8 / 32 * 1.25) = 20 of a group's
        assert cfg["assumed"]["moe_group_tokens"] == 64
        mix.update(batch=8)
    else:
        cfg.update(intermediate_size=512, sliding_window=32)
        rep.update(d_ff=512, window=32)
        # 4 rows a chip, as the cells' 16 rows on four chips
        mix.update(batch=4 * c["workload"]["chips"])
    return c
