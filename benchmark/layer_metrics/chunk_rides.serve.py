"""Share of the prompt chunks dispatched in the traced stretch that carried
decoding rows: the ``serve/prefill_chunk`` spans whose ``rode`` (the
decoding slots whose token rode in the chunk's program) is above 0. Where
a chunk rides, each weight of a layer's tail and each touched expert is
read once for the chunk's rows and the decode rows together. A program
whose chunk spans carry no ``rode`` reads nothing."""


def read(ctx):
    from benchmark import program_spans as ps
    chunks = [s.attrs for s in ps.in_stretch(ctx)
              if s.name == "serve/prefill_chunk" and "rode" in s.attrs]
    if not chunks:
        return None
    rode = [c["rode"] for c in chunks if c["rode"] > 0]
    ctx["notes"].append(
        f"chunk_rides.serve: {len(rode)} of {len(chunks)} chunks carried "
        f"decoding rows, {sum(rode) / max(len(rode), 1):.1f} a chunk")
    return 100.0 * len(rode) / len(chunks)
