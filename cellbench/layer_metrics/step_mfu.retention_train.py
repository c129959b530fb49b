"""The whole training step's share of the chip's peak, for a decoder of
degree-2 power-retention layers: operations the forward and backward passes
REQUIRE for the steps finished in the window (`cellbench/flops_retention.py`:
4 per frozen weight a token touches, 6 per adapter weight, the retention
scan's products in the chunked form over the exact symmetric square at the
configured chunk; remat's recomputation never counted), over the window and
the peak.

None for a configuration without a `power_retention` layer."""

from cellbench import flops_retention


def read(obs):
    peaks, cfg = obs.get("peaks"), obs.get("config") or {}
    if not peaks or not obs.get("steps") or "power_retention" not in (
        cfg.get("layer_types") or ()
    ):
        return None
    lora = obs["cell"]["reference"].get("lora") or {}
    per_step = flops_retention.train_step_flops(
        cfg, obs["rows"], obs["seq_len"],
        lora_rank=int(lora.get("rank", 0)), lora_targets=lora.get("targets", ()),
    )["total"]
    achieved = per_step * obs["steps"] / obs["window_s"]
    return 100.0 * achieved / (peaks["flops_per_s"] * obs["chips"])
