"""One scoped part's share of its own roofline in the train step: the least
time the chip could take for the part's needed work at the cell's shapes
(the larger of FLOPs/peak and bytes/peak; the architecture's
``kernel_work(config, job, part)``), over the part's device time per traced
step: the operations under the scope (``scope_ms``'s patterns) and those
named in ``ops`` (``<name>`` of ``<program>/<name>`` in the trace: a
kernel the compiler makes itself carries no ``op_name``, so no scope finds
it).  Whatever runs there counts as the part's time, a kernel as much as
the fusions around it; work done again by remat counts for nothing.  None
where the traced program has no such scope or the architecture no such
part."""

from benchmarks.suite import archs, work
from benchmarks.suite.readers import scope_ms


def read(context, part: str, under: list = (), not_under: list = (),
         ops: list = ()):
    if not context["require_tpu"]:
        return None  # a CPU rehearsal has no peak to take a share of
    took_ms = scope_ms.read(context, under, not_under)
    if not took_ms:
        return None
    named = sum(seconds for name, seconds in context["trace"]["ops"].items()
                if name.split("/", 1)[-1] in ops)
    took = took_ms * 1e-3 + named / context["trace_steps"]
    config, job = context["cell"]["config"], context["cell"]["traffic"]
    try:
        needed = archs.load(config).kernel_work(config, job, part)
    except KeyError:
        return None
    seconds, _ = work.roofline_seconds(
        needed, work.peaks(context["device"]["kind"]), context["chips"])
    return 100.0 * seconds / took
