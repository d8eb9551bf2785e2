"""Host milliseconds spent building a step's rows: the mean of the
program's own ``input.build`` spans
(``SyntheticLMDataset.global_batch_arrays``) that start in the traced
window."""


def read(obs):
    spans = obs.get("trace", {}).get("program_spans", {}).get("input.build")
    return 1e3 * sum(spans) / len(spans) if spans else None
