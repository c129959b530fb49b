"""The whole training step's share of the chip's peak, for a hybrid decoder
of delta-rule (KDA) and latent-attention (MLA) layers with routed experts:
operations the forward and backward passes REQUIRE for the steps finished in
the window (`cellbench/flops_kda.py`: 4 per frozen weight a token touches,
the routed experts in expectation; 6 per adapter weight; the MLA triangle at
its score and value widths; the delta rule's products in the chunked form at
chunk 64; remat's recomputation never counted), over the window and the peak.

None for a configuration without a `kda` layer."""

from cellbench import flops_kda


def read(obs):
    peaks, cfg = obs.get("peaks"), obs.get("config") or {}
    if not peaks or not obs.get("steps") or "kda" not in (cfg.get("layer_types") or ()):
        return None
    lora = obs["cell"]["reference"].get("lora") or {}
    per_step = flops_kda.train_step_flops(
        cfg, obs["rows"], obs["seq_len"],
        lora_rank=int(lora.get("rank", 0)), lora_targets=lora.get("targets", ()),
    )["total"]
    achieved = per_step * obs["steps"] / obs["window_s"]
    return 100.0 * achieved / (peaks["flops_per_s"] * obs["chips"])
