"""The whole serving step's share of the chip's peak: operations REQUIRED
for the tokens processed in the window (every block's linear layers per
token prefilled or decoded, attention against the keys before it, the head
where a token is sampled; cellbench/flops.py), over the window and the peak.
Padding to buckets and slices is not required work and is not counted."""


def read(obs):
    peaks, work = obs.get("peaks"), obs.get("work")
    if not peaks or not work or not work["flops"]:
        return None
    return 100.0 * work["flops"] / obs["window_s"] / (peaks["flops_per_s"] * obs["chips"])
