"""paddle_tpu.serving — the production model server (ISSUE 8,
docs/serving.md).

A server process hosts N models, each as a set of AOT-compiled
shape-bucket executables warmed at startup; requests coalesce through a
bounded admission queue into continuously-formed batches that land on
compiled buckets via pad-and-slice; the transformer family serves
autoregressive traffic through the paged slot engine (a prefill per
admission, one fixed-shape decode step over every slot: O(1) per token,
zero steady-state compiles). The client wraps the
distributed/resilience.py kit (RetryPolicy + CircuitBreaker) and every
stage exports through observability/ (scrape endpoint included).

Public surface::

    from paddle_tpu import serving
    policy = serving.BucketPolicy.pow2(8)
    server = serving.ModelServer()
    server.add_model(serving.ServedModel("clf", model_dir, policy))
    server.add_model(serving.make_slot_model("lm", programs))
    endpoint = server.serve()
    client = serving.ServingClient(endpoint)
    outs = client.infer("clf", {"x": batch})
    toks = client.generate("lm", prompts, max_new=32)

Submodules import lazily (PEP 562) so light consumers — the predictor's
AOT-fallback counter, the exporter catalog — can import
``paddle_tpu.serving.metrics`` without pulling the whole server stack.
"""

from __future__ import annotations

_LAZY = {
    "BucketPolicy": ("paddle_tpu.serving.bucketing", "BucketPolicy"),
    "FeedSignature": ("paddle_tpu.serving.bucketing", "FeedSignature"),
    "pad_to_bucket": ("paddle_tpu.serving.bucketing", "pad_to_bucket"),
    "slice_outputs": ("paddle_tpu.serving.bucketing", "slice_outputs"),
    "ServedModel": ("paddle_tpu.serving.engine", "ServedModel"),
    "GenerativeModel": ("paddle_tpu.serving.engine", "GenerativeModel"),
    "SlotGenerativeModel": ("paddle_tpu.serving.engine",
                            "SlotGenerativeModel"),
    "make_slot_model": ("paddle_tpu.serving.engine", "make_slot_model"),
    "PagePool": ("paddle_tpu.serving.kv_pool", "PagePool"),
    "PagesExhaustedError": ("paddle_tpu.serving.kv_pool",
                            "PagesExhaustedError"),
    "SlotExhaustedError": ("paddle_tpu.serving.engine",
                           "SlotExhaustedError"),
    "PromptTooLongError": ("paddle_tpu.serving.engine",
                           "PromptTooLongError"),
    "ModelServer": ("paddle_tpu.serving.server", "ModelServer"),
    "Router": ("paddle_tpu.serving.router", "Router"),
    "ROUTER_ENV": ("paddle_tpu.serving.router", "ROUTER_ENV"),
    "Autoscaler": ("paddle_tpu.serving.autoscaler", "Autoscaler"),
    "AutoscalePolicy": ("paddle_tpu.serving.autoscaler",
                        "AutoscalePolicy"),
    "RouterSource": ("paddle_tpu.serving.autoscaler", "RouterSource"),
    "PlacementError": ("paddle_tpu.serving.autoscaler",
                       "PlacementError"),
    "bin_pack": ("paddle_tpu.serving.autoscaler", "bin_pack"),
    "plan_placement": ("paddle_tpu.serving.autoscaler",
                       "plan_placement"),
    "validate_host": ("paddle_tpu.serving.autoscaler", "validate_host"),
    "RequestShedError": ("paddle_tpu.serving.server", "RequestShedError"),
    "ReplicaDrainingError": ("paddle_tpu.serving.server",
                             "ReplicaDrainingError"),
    "RequestCancelledError": ("paddle_tpu.serving.server",
                              "RequestCancelledError"),
    "ModelNotFoundError": ("paddle_tpu.serving.server",
                           "ModelNotFoundError"),
    "SERVING_ENV": ("paddle_tpu.serving.server", "SERVING_ENV"),
    "ServingClient": ("paddle_tpu.serving.client", "ServingClient"),
    "ServingUnavailableError": ("paddle_tpu.serving.client",
                                "ServingUnavailableError"),
    "ServingRequestError": ("paddle_tpu.serving.client",
                            "ServingRequestError"),
    "forbid_compiles": ("paddle_tpu.serving.metrics", "forbid_compiles"),
    "CompileForbiddenError": ("paddle_tpu.serving.metrics",
                              "CompileForbiddenError"),
    "metrics": ("paddle_tpu.serving.metrics", None),
    "bucketing": ("paddle_tpu.serving.bucketing", None),
    "engine": ("paddle_tpu.serving.engine", None),
    "server": ("paddle_tpu.serving.server", None),
    "client": ("paddle_tpu.serving.client", None),
    "router": ("paddle_tpu.serving.router", None),
    "replica": ("paddle_tpu.serving.replica", None),
    "autoscaler": ("paddle_tpu.serving.autoscaler", None),
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    entry = _LAZY.get(name)
    if entry is None:
        raise AttributeError(f"module 'paddle_tpu.serving' has no "
                             f"attribute {name!r}")
    import importlib
    mod = importlib.import_module(entry[0])
    value = mod if entry[1] is None else getattr(mod, entry[1])
    globals()[name] = value
    return value


def __dir__():
    return __all__
