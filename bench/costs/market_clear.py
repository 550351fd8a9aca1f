"""Operations and HBM bytes of one whole-market ``market_clear`` launch,
from its shapes (the analytic model of ``benchmarks/roofline.py``
``megakernel_roofline``, kept here for a later cell): per dual trip the
demand-and-slope tile runs an ``inner_iters``-deep bisection (about 6
operations per lane per trip) and the closed-form slope sums (about 12 per
lane); alpha and t^C cross HBM once, and b, f and the price go out."""


def cost(n: int, k_pad: int, trips: int, inner_iters: int
         ) -> tuple[float, float]:
    flops = trips * n * k_pad * (6 * inner_iters + 12)
    nbytes = (2 * n * k_pad + 3 * n) * 4
    return float(flops), float(nbytes)
