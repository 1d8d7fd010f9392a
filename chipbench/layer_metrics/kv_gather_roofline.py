"""The paged decode's row gather against the HBM roofline: the bytes it
must move in a step (``flops.kv_gather_bytes_per_step``: every cache
row of every slot, K and V, every layer, read once and written once)
over the peak bandwidth, as a share of the kernels' time. HBM-bound: the
gather does no arithmetic."""

from chipbench import flops
from chipbench import trace_reduce as tr


def read(obs):
    red, build = obs["reduced"], obs["config"]["build"]
    spans = tr.spans_named(red, "serving.decode_step")
    steps = tr.ops_of_spans(red, spans)[1] if spans else 0
    if not steps:
        return None
    seconds = tr.mean_seconds(red, tr.is_custom_call, spans)
    if seconds == 0.0:
        return None
    rows = build["n_slots"] * (build["prompt_len"] + build["max_new"])
    itemsize = {"none": 4, "bf16": 2, "int8": 1}[obs["config"]["kv_codec"]]
    bytes_ = flops.kv_gather_bytes_per_step(
        rows, build["d_model"], itemsize, build["n_layer"]) * steps
    return flops.roofline_pct(0.0, bytes_, seconds, obs["peaks"])
