"""Share of the traced window's trainings whose segmented k-means fit
re-dispatched its unconverged segments: the program's
``kmeans.stragglers`` spans over its ``lern.train`` spans
(``chipbench/spans.py``)."""
from chipbench import spans


def read(ctx):
    return spans.redispatch_share(ctx.trace)
