"""Mean share of the page pool's pages that were not on the free list
while the window was open (1 - ``paddle_kv_pages_free`` over
``paddle_kv_pages_total``, the pool's own gauges, sampled every 0.1 s),
in %. A page is held by a running request's lease, which covers its
prompt bucket and its whole budget of new tokens, or, after the request
has completed, as evictable prefix cache (its full prompt pages stay in
the radix tree until an admission needs them): the program has no gauge
that tells the two apart, so the share grows with the window."""


def read(obs):
    share = obs.get("kv_pages_held")
    return None if share is None else 100.0 * share
