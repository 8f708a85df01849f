"""What decides `correct`: the timed path's own logits and served tokens
against the plain reference.

During the window the decode program's output row is kept (sliced on the
device by one small compiled program, warmed in set-up) at the step that
produces each request's first answer token and at the step that produces
its last one; the step's other slots go on as they would.  After the window
and the drain, a sample of the finished requests is drawn from the seed,
the longest of them always in it.  Once the program's state is
freed, the reference runs over each sampled request's prompt and served
tokens and gives two numbers:

  max_logit_err  the largest |program - reference| over the vocabulary at
                 every kept row (the first row reads what chunked prefill
                 wrote into the pages, the last what decode wrote);
  max_token_gap  the widest gap by which a served token's reference logit
                 lies below the reference's best, over every served token.

With the precision control, the same two numbers are read with the
reference computed at `high` (three bf16 passes) in the program's place,
and once more at one bf16 pass."""
from __future__ import annotations

import dataclasses
import functools
import gc
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import weights as W

SAMPLE = 8          # requests checked per run


def draw_sample(finished: Dict[int, int], seed: int) -> List[int]:
    """rids to check, from ``finished`` (rid -> prompt + answer tokens):
    the longest, then others drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda rid: (finished[rid], -rid))
    rest = sorted(rid for rid in finished if rid != longest)
    rng = np.random.default_rng([seed, 1])
    k = min(SAMPLE - 1, len(rest))
    return [longest] + [int(r) for r in rng.choice(rest, k, replace=False)]


@jax.jit
def _row(logits, i):
    return logits[i, -1]


def warm_row(logits):
    """Compiles the row slice for the decode program's output in set-up."""
    jax.block_until_ready(_row(logits, np.int32(0)))


class Capture:
    """Keeps the decode program's output row at every request's first- and
    last-token step.  Wraps the batcher's compiled decode call; the wrapped
    call is the same compiled program."""

    def __init__(self, batcher, plan):
        self.batcher = batcher
        # the row fed at position pos predicts the token at pos + 1
        self.targets: Dict[int, Tuple[int, int]] = {
            p.rid: (len(p.prompt) - 1, len(p.prompt) + p.max_new - 2)
            for p in plan}
        self.kept: List[tuple] = []             # (rid, pos, device row)
        self.rows: List[Tuple[int, int]] = []   # (rid, pos) of each row
        self.host_rows = None
        self._orig = batcher._step
        batcher._step = self._wrapped

    def _wrapped(self, *args):
        out = self._orig(*args)
        for i, s in enumerate(self.batcher.slots):
            r = s.req
            if r is not None and s.pos in self.targets.get(r.rid, ()):
                self.kept.append((r.rid, s.pos, _row(out[0], np.int32(i))))
        return out

    def unhook(self):
        self.batcher._step = self._orig

    def collect(self, rids):
        """The kept rows of ``rids`` to the host; every device row
        dropped."""
        want = set(rids)
        rows = []
        for rid, pos, arr in self.kept:
            if rid in want:
                rows.append(np.asarray(arr))
                self.rows.append((rid, pos))
        self.kept = []
        self.host_rows = np.stack(rows) if rows else None


@dataclasses.dataclass
class Readings:
    max_logit_err: float
    max_token_gap: float
    rows: int
    tokens: int


def layer_step(ref, c: dict, dot, S_dev: int, T: int):
    """The compiled reference block over (S, T, d) hidden states, S_dev
    sequences to a device; causal attention in blocks of query rows that
    keep the scores near 512 MiB."""
    q_block = int(max(16, min(512, (512 << 20)
                              // (S_dev * c["num_attention_heads"] * T * 4))))

    @jax.jit
    def step(w, h):
        w = ref.layer_weights(jax.tree.map(lambda a: a[None], w), 0)
        return ref.layer(c, dot, w, h, q_block)

    return step


def _hidden(ref, c, dot, layout, key, rest, tokens, whole):
    """Final hidden states of the reference over tokens (S, T), sharded on
    S; one layer's weights, copied to every device, at a time."""
    h = jnp.take(rest["embed"]["table"], tokens, axis=0)
    S, T = tokens.shape
    step = layer_step(ref, c, dot, S // len(whole.mesh.devices.flat), T)
    make_layer = W.layer_maker(layout, whole)
    for i in range(c["num_hidden_layers"]):
        # one layer's weights alive at a time
        h = step(make_layer(key, i), h).block_until_ready()
    return h


def readings(ref, c: dict, layout: dict, seed: int, prompts: dict,
             outputs: dict, capture: Capture, max_len: int, devices,
             control: bool = False) -> Dict[str, Readings]:
    """{"program": ...} and, with ``control``, {"control": ...}: the
    reference at `high` put in the program's place, its logits at the kept
    rows and its own first choice at every served position, each read
    against the reference; {"control_bf16": ...}, the same at one bf16
    pass; and {"token_fault": ...}: the token gap had each
    served token been altered (+1) where it was produced.  ``outputs`` are
    the sampled requests' served tokens.  The sequences are split over
    ``devices`` and every weight is copied to each, one layer at a time."""
    served = [(rid, len(prompts[rid]), np.asarray(out, np.int32))
              for rid, out in outputs.items() if len(out)]
    if not served:
        raise RuntimeError("no sampled request was served a token")
    n_dev = len(devices)
    S = -(-len(served) // n_dev) * n_dev   # padding rows repeat row 0
    P = -(-max(len(o) for _, _, o in served) // 256) * 256   # few shapes
    tokens = np.zeros((S, max_len), np.int32)
    pos = np.zeros((S, P), np.int32)
    tok = np.zeros((S, P), np.int32)
    valid = np.zeros((S, P), bool)
    for s in range(S):
        rid, plen, out = served[s if s < len(served) else 0]
        n = len(out)
        seq = np.concatenate([prompts[rid], out])[:max_len]
        tokens[s, :len(seq)] = seq
        pos[s] = plen - 1 + np.minimum(np.arange(P), n - 1)
        tok[s] = out[np.minimum(np.arange(P), n - 1)]
        valid[s, :n] = s < len(served)
    index = {rid: s for s, (rid, _, _) in enumerate(served)}
    keep = [i for i, (rid, _) in enumerate(capture.rows) if rid in index]
    rpos = np.array([[index[capture.rows[i][0]], capture.rows[i][1]]
                     for i in keep], np.int32).reshape(-1, 2)
    mesh = Mesh(np.array(devices), ("s",))
    rows_sh = NamedSharding(mesh, PartitionSpec("s"))
    whole = NamedSharding(mesh, PartitionSpec())
    key = W.seed_key(seed)
    rest = W.make_rest(layout, key, whole)
    head = ref.head_matrix(c, rest)
    readout = {name: jax.jit(functools.partial(ref.head_readout, c, dot))
               for name, dot in ref.DOTS.items()}
    jtok = jax.device_put(tokens, rows_sh)
    jpos = jax.device_put(pos, rows_sh)

    def rows_of(dot, h):
        if not len(rpos):
            return None
        x = ref.rms_norm(h[rpos[:, 0], rpos[:, 1]], rest["ln_f"])
        return np.asarray(dot("pd,dv->pv", x, head))

    def widest(gap):
        return float(np.max(np.where(valid, gap, 0.0)))

    h = _hidden(ref, c, ref.DOTS["highest"], layout, key, rest, jtok, whole)
    best, at, _ = (np.asarray(a) for a in readout["highest"](
        rest["ln_f"], head, h, jpos, jax.device_put(tok, rows_sh)))
    full = rows_of(ref.DOTS["highest"], h)
    prog = capture.host_rows[keep] if keep else None
    out = {"program": Readings(
        max_logit_err=(float(np.max(np.abs(prog - full)))
                       if full is not None else float("nan")),
        max_token_gap=widest(best - at), rows=len(keep),
        tokens=int(valid.sum()))}
    if control:
        for name, dot in (("control", "high"), ("control_bf16", "bf16")):
            hc = _hidden(ref, c, ref.DOTS[dot], layout, key, rest, jtok,
                         whole)
            _, _, first = readout[dot](rest["ln_f"], head, hc, jpos,
                                       jax.device_put(tok, rows_sh))
            _, at_c, _ = readout["highest"](rest["ln_f"], head, h, jpos,
                                            first)
            full_c = rows_of(ref.DOTS[dot], hc)
            del hc
            out[name] = Readings(
                max_logit_err=(float(np.max(np.abs(full_c - full)))
                               if full is not None else float("nan")),
                max_token_gap=widest(best - np.asarray(at_c)),
                rows=len(keep), tokens=int(valid.sum()))
        # a token altered where it is produced: each served token + 1
        _, at_f, _ = readout["highest"](
            rest["ln_f"], head, h, jpos,
            jax.device_put((tok + 1) % c["vocab_size"], rows_sh))
        out["token_fault"] = Readings(
            max_logit_err=float("nan"),
            max_token_gap=widest(best - np.asarray(at_f)), rows=0,
            tokens=int(valid.sum()))
    del h, rest, head
    gc.collect()
    return out
