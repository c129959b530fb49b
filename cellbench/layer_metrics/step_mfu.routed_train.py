"""The whole training step's share of the chip's peak, for a decoder of mixed
window/full attention with routed experts: operations the forward and
backward passes REQUIRE for the steps finished in the window
(`cellbench/flops_routed.py`: 4 per frozen weight a token touches, the routed
experts in expectation; 6 per adapter weight; attention over the window's
pairs in sliding layers and the causal triangle in full ones; remat's
recomputation not counted), over the window and the peak.

None for a configuration that is not of this kind (no `layer_types`)."""

from cellbench import flops_routed


def read(obs):
    peaks, cfg = obs.get("peaks"), obs.get("config") or {}
    if not peaks or not obs.get("steps") or "layer_types" not in cfg:
        return None
    lora = obs["cell"]["reference"].get("lora") or {}
    per_step = flops_routed.train_step_flops(
        cfg, obs["rows"], obs["seq_len"],
        lora_rank=int(lora.get("rank", 0)), lora_targets=lora.get("targets", ()),
    )["total"]
    achieved = per_step * obs["steps"] / obs["window_s"]
    return 100.0 * achieved / (peaks["flops_per_s"] * obs["chips"])
