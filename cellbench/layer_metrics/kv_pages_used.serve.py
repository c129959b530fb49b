"""Share of the KV pool's pages in use: /statsz `kv.pages_used` over
`kv.pages_total`, sampled once a second in the window, mean. Pages the
prefix cache holds for finished prompts count as used (they are)."""


def read(obs):
    samples = [s for s in obs.get("kv_samples") or [] if s.get("pages_total")]
    if not samples:
        return None
    return 100.0 * sum(s["pages_used"] / s["pages_total"] for s in samples) / len(samples)
