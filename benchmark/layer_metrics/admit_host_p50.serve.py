"""Median duration of ``serve/admit`` in the traced stretch: the host's
side of one admission, from the page reservation to the prefill's record
joining the harvest queue (plan of the page runs, the prefill enqueue, the
slot-state updates). The notes give the median per prefill ``bucket``."""
import statistics


def read(ctx):
    from benchmark import program_spans as ps
    admits = [s for s in ps.in_stretch(ctx) if s.name == "serve/admit"]
    if not admits:
        return None
    by_bucket = {}
    for s in admits:
        by_bucket.setdefault(s.attrs.get("bucket"), []).append(
            ps.seconds(s) * 1e3)
    ctx["notes"].append(
        f"admit_host_p50.serve over {len(admits)} admissions; median ms "
        f"by bucket: " + ", ".join(
            f"{b}: {statistics.median(v):.3f} ({len(v)})"
            for b, v in sorted(by_bucket.items(),
                               key=lambda kv: (kv[0] is None, kv[0]))))
    return statistics.median(ps.seconds(s) for s in admits) * 1e3
