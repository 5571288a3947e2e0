"""Mean per light reply of the work after the device has answered (ms):
``*.finalize`` (filters, projection, ``_final_process``), ``template.commit``
and ``proxy.reply`` (admission and reuse accounting)."""
from benchmark.program_spans import span_ms, traced
from benchmark.stats import mean


def read(run):
    return mean(span_ms(r, names=("template.commit", "proxy.reply"),
                        suffixes=(".finalize",))
                for r in traced(run, "light"))
