"""Host milliseconds per step spent building a step's rows
(``SyntheticLMDataset.global_batch_arrays``) and putting them on the
chips (``jax.device_put``): the mean of the benchmark's ``input`` spans in
the traced window."""


def read(obs):
    spans = obs.get("trace", {}).get("host_spans", {}).get("input", [])
    return 1e3 * sum(spans) / len(spans) if spans else None
