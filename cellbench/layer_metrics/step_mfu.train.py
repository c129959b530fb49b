"""The whole training step's share of the chip's peak: operations the
forward and backward passes REQUIRE for the steps finished in the window
(cellbench/flops.py: 4 per frozen weight and token, 6 per trainable one,
causal attention forward and backward, the head inside the loss;
recomputation under remat not counted), over the window and the peak."""

from cellbench import flops


def read(obs):
    peaks = obs.get("peaks")
    if not peaks or not obs.get("steps"):
        return None
    lora = obs["cell"]["reference"].get("lora") or {}
    per_step = flops.train_step_flops(
        obs["config"], obs["rows"], obs["seq_len"],
        lora_rank=int(lora.get("rank", 0)), lora_targets=lora.get("targets", ()),
    )["total"]
    achieved = per_step * obs["steps"] / obs["window_s"]
    return 100.0 * achieved / (peaks["flops_per_s"] * obs["chips"])
