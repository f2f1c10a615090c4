"""Device planes for a traced run on the CPU, whose trace has no TPU
plane: each used device is made busy exactly inside the benchmark's call
spans.  As ops, every call is one op named ``op``; as programs, a Newton
step's factorize span is ``glu_factorize`` and its solve span
``glu_trisolve``, and a sweep call's first half ``glu_factorize`` and its
second half ``glu_trisolve``."""
import pytest

import program_trace as pt
import trace as tm

CALLS = ("bench.factorize", "bench.solve", "bench.sweep")


def _programs(spans):
    out = []
    for name, s, e in spans:
        if name == "bench.factorize":
            out.append(("glu_factorize", s, e))
        elif name == "bench.solve":
            out.append(("glu_trisolve", s, e))
        elif name == "bench.sweep":
            mid = 0.5 * (s + e)
            out += [("glu_factorize", s, mid), ("glu_trisolve", mid, e)]
    return out


def install(mp):
    """Patch ``trace.read`` and ``program_trace.read`` for this process."""
    real_ops, real_programs = tm.read, pt.read

    def read_ops(path, device_ids):
        with pytest.raises(ValueError, match="no device plane"):
            real_ops(path, device_ids)
        spans, _ = real_ops(path, [])
        calls = [("op", s, e) for n, s, e in spans if n in CALLS]
        return spans, {f"/device:TPU:{i}": calls for i in device_ids}

    def read_programs(path, device_ids):
        spans, _, _ = real_programs(path, [])
        planes = {f"/device:TPU:{i}": _programs(spans) for i in device_ids}
        return spans, planes, dict.fromkeys(planes, 0.0)

    mp.setattr(tm, "read", read_ops)
    mp.setattr(pt, "read", read_programs)
