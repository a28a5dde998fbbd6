"""``serve.admit_ms_per_ktok``: the summed duration of the program's
``serve.admit`` spans (``repro.obs.trace``, host clock: one per admitted
request, from its page allocation to its first token on the host) over the
prompt tokens they ran through prefill (the spans' ``tokens``), per 1000
tokens."""


def read(rec):
    if not rec.spans:
        return None
    admits = [e for e in rec.spans if e["name"] == "serve.admit"]
    tokens = sum(e["args"].get("tokens", 0) for e in admits)
    return 1000.0 * sum(e["dur"] * 1e-3 for e in admits) / tokens if tokens else None
