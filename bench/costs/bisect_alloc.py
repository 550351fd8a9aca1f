"""Operations and HBM bytes one ``kernels.bisect_alloc`` call needs, from
its shapes: N services of K clients, ``iters`` bisection trips (unpadded).

Per client lane, per trip: u + gap, the floor, the division and the sum
(4); per service, per trip, the midpoint, the residual and the comparison
(4).  Set-up and the final split cost 8 per lane.  Bytes: alpha and t^C
in, b in, t* out and the (N, K) split out, in float32."""


def cost(n: int, k: int, iters: int) -> tuple[float, float]:
    flops = n * (iters * (4 * k + 4) + 8 * k)
    nbytes = 4 * (3 * n * k + 2 * n)
    return float(flops), float(nbytes)
