"""Model FLOP utilisation of the whole train step: model FLOPs per token
(the reference module, recompute not counted) times the traced window's
training tokens per second per chip, over the chip's bf16 peak
(bench/peaks.json), in percent."""


def read(obs):
    peaks = obs.get("peaks")
    if not peaks or not obs.get("steps"):
        return None
    return (100.0 * obs["flops_per_token"] * obs["tokens_per_s_per_chip"]
            / peaks["bf16_flops_per_s"])
