"""Plain reference of fleet scoring: preprocessing (paper §III-B), the
per-chain execution graph and the Perona forward pass (§III-C), in
straightforward numpy and ``jax.numpy``.

It imports nothing of the program and takes nothing the program made:
it fits its own feature statistics on the deployment's fit
acquisition, builds each new execution's graph from the generated
telemetry, and runs the forward pass on the benchmark's own weights.
A new execution's score depends on its own chain only, on at most
``predecessors * tag_hops`` rows before it (one TransformerConv hop,
``tag_hops`` TAGConv hops), so the reference builds exactly that
window per chain; the program's wider context gives the same score.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import numpy as np

#: Unit -> multiplier to the canonical unit of its family (s, MiB,
#: MiB/s, ratio, 1/s, count), as the paper's unification step does.
UNIT_SCALE: Dict[str, float] = {
    "s": 1.0, "ms": 1e-3, "us": 1e-6, "ns": 1e-9, "min": 60.0,
    "bytes": 1.0 / (1024 * 1024), "KiB": 1.0 / 1024, "MiB": 1.0,
    "GiB": 1024.0, "MB": 0.95367,
    "KiB/s": 1.0 / 1024, "MiB/s": 1.0, "GiB/s": 1024.0, "MB/s": 0.95367,
    "bps": 1.0 / (8 * 1024 * 1024), "Kbps": 1e3 / (8 * 1024 * 1024),
    "Mbps": 1e6 / (8 * 1024 * 1024), "Gbps": 1e9 / (8 * 1024 * 1024),
    "%": 0.01, "ratio": 1.0,
    "K/s": 1e3, "iops": 1.0, "ops/s": 1.0, "events/s": 1.0, "1/s": 1.0,
    "count": 1.0, "events": 1.0, "ops": 1.0,
}


def merged(frame) -> Dict[str, tuple]:
    """Metric name -> (values (N,), present (N,)) in canonical units;
    a name recorded under several units is one column."""
    out: Dict[str, tuple] = {}
    n = len(frame.t)
    for c, (name, unit) in enumerate(zip(frame.metric_names,
                                         frame.metric_units)):
        pres = frame.metrics_present[:, c]
        val = frame.metrics[:, c] * UNIT_SCALE.get(unit, 1.0)
        v, p = out.get(name, (np.zeros(n), np.zeros(n, bool)))
        out[name] = (np.where(pres, val, v), p | pres)
    return out


@dataclasses.dataclass
class Features:
    """Feature statistics fitted on the fit acquisition."""

    names: List[str]
    maximize: np.ndarray
    lo: np.ndarray
    hi: np.ndarray
    fill: np.ndarray
    types: List[str]
    edge_names: List[str]
    edge_lo: np.ndarray
    edge_hi: np.ndarray

    @classmethod
    def fit(cls, frame, cv_threshold: float = 0.02) -> "Features":
        cols = merged(frame)
        names, maximize, lo, hi = [], [], [], []
        for name in sorted(cols):
            v, p = cols[name]
            a = v[p]
            if len(np.unique(np.round(a, 12))) < 2:
                continue
            if np.std(a) / max(abs(np.mean(a)), 1e-12) < cv_threshold:
                continue
            mn, mx, med = a.min(), a.max(), np.median(a)
            names.append(name)
            maximize.append(mx - med <= med - mn)
            lo.append(mn)
            hi.append(mx if mx > mn else mn + 1.0)
        f = cls(names=names, maximize=np.asarray(maximize),
                lo=np.asarray(lo), hi=np.asarray(hi), fill=None,
                types=sorted({frame.benchmark_types[c]
                              for c in np.unique(frame.type_code)}),
                edge_names=[], edge_lo=None, edge_hi=None)
        raw, pres = f.raw(frame)
        norm = f.normalize(raw)
        f.fill = (norm * pres).sum(0) / np.maximum(pres.sum(0), 1)
        f.edge_names = sorted(
            name for i, name in enumerate(frame.node_metric_names)
            if frame.node_metrics_present[:, i].any())
        e = f.edges(frame)
        f.edge_lo = e.min(0)
        f.edge_hi = np.where(e.max(0) > e.min(0), e.max(0), e.min(0) + 1)
        return f

    def raw(self, frame):
        cols = merged(frame)
        n = len(frame.t)
        raw = np.zeros((n, len(self.names)))
        pres = np.zeros((n, len(self.names)), bool)
        for i, name in enumerate(self.names):
            if name in cols:
                raw[:, i], pres[:, i] = cols[name]
        return raw, pres

    def normalize(self, raw):
        norm = np.clip((raw - self.lo) / (self.hi - self.lo), 0.0, 1.0)
        return np.where(self.maximize, norm, 1.0 - norm)

    def edges(self, frame):
        idx = {n: i for i, n in enumerate(frame.node_metric_names)}
        out = np.zeros((len(frame.t), len(self.edge_names)))
        for j, name in enumerate(self.edge_names):
            if name in idx:
                c = idx[name]
                out[:, j] = np.where(frame.node_metrics_present[:, c],
                                     frame.node_metrics[:, c], 0.0)
        return out

    def type_ids(self, frame):
        index = {t: i for i, t in enumerate(self.types)}
        return np.asarray([index[frame.benchmark_types[c]]
                           for c in frame.type_code])

    @property
    def feature_dim(self) -> int:
        return len(self.names) + len(self.types)

    @property
    def edge_dim(self) -> int:
        return len(self.edge_names) + 4


def rows(feats: Features, frame) -> Dict[str, np.ndarray]:
    raw, pres = feats.raw(frame)
    return {"raw": raw, "present": pres, "type": feats.type_ids(frame),
            "edge_raw": feats.edges(frame), "t": np.asarray(frame.t)}


def chain_windows(chains: List[Dict[str, np.ndarray]], depth: int):
    """Graph of one node's new executions: for each chain (rows of one
    benchmark type, chronological, the new execution last) the newest
    ``depth + 1`` rows, each row linked to its ``predecessors`` newest
    earlier rows. Returns per-row arrays and the new rows' indices."""
    keep = [{k: v[-(depth + 1):] for k, v in c.items()} for c in chains]
    cat = {k: np.concatenate([c[k] for c in keep]) for k in keep[0]}
    starts = np.cumsum([0] + [len(c["t"]) for c in keep])
    return cat, starts, starts[1:] - 1


def graph(t: np.ndarray, starts: np.ndarray, p: int):
    """Predecessor index, mask, time gap and source time per row, with
    chains stored contiguously and chronologically."""
    n = len(t)
    nbr = -np.ones((n, p), np.int64)
    for c in range(len(starts) - 1):
        for i in range(starts[c], starts[c + 1]):
            for q in range(p):
                j = i - 1 - q
                if j >= starts[c]:
                    nbr[i, q] = j
    src = np.maximum(nbr, 0)
    mask = nbr >= 0
    dt = np.where(mask, np.maximum(t[:, None] - t[src], 0.0), 0.0)
    t_src = np.where(mask, t[src], 0.0)
    return nbr, mask, dt, t_src


def forward(params, inputs, feats: Features, model: dict, dtype):
    """Anomaly probability and codes of every row of one graph, every
    operation in ``dtype`` (matmuls at the precision in force)."""
    import jax
    import jax.numpy as jnp

    def c(a):
        return jnp.asarray(a).astype(dtype)

    def lin(p, v):
        return v @ c(p["w"]) + c(p["b"])

    def mlp(layers, v):
        for i, p in enumerate(layers):
            v = lin(p, v)
            if i + 1 < len(layers):
                v = jax.nn.selu(v)
        return v

    raw, pres = c(inputs["raw"]), inputs["present"]
    norm = jnp.clip((raw - c(feats.lo)) / (c(feats.hi) - c(feats.lo)),
                    0.0, 1.0)
    norm = jnp.where(feats.maximize, norm, 1.0 - norm)
    norm = jnp.where(pres, norm, c(feats.fill))
    onehot = jax.nn.one_hot(inputs["type"], len(feats.types), dtype=dtype)
    x = jnp.concatenate([norm, onehot], axis=-1)

    nbr, mask = inputs["nbr"], inputs["mask"]
    src = jnp.maximum(nbr, 0)
    e_raw = c(inputs["edge_raw"])[src]
    efeat = jnp.clip((e_raw - c(feats.edge_lo))
                     / (c(feats.edge_hi) - c(feats.edge_lo)), 0.0, 1.0)
    dt, t_src = c(inputs["dt"]), c(inputs["t_src"])
    ang = 2 * math.pi * ((t_src / 3600.0) % 24.0) / 24
    enc = jnp.stack([jnp.log1p(dt) / 12.0, jnp.minimum(dt / 3600.0, 1.0),
                     0.5 + 0.5 * jnp.sin(ang), 0.5 + 0.5 * jnp.cos(ang)],
                    axis=-1)
    edge = jnp.where(mask[..., None],
                     jnp.concatenate([efeat, enc], -1), 0.0)

    codes = mlp(params["enc"], x)
    k_dim, heads = model["code_dim"], model["heads"]
    hd = k_dim // heads
    n, p = mask.shape
    nb = codes[src]
    q = lin(params["wq"], codes).reshape(n, heads, hd)
    key = (lin(params["wk"], nb) + lin(params["we_k"], edge)
           ).reshape(n, p, heads, hd)
    val = (lin(params["wv"], nb) + lin(params["we_v"], edge)
           ).reshape(n, p, heads, hd)
    s = jnp.einsum("nhf,nphf->nhp", q, key) / math.sqrt(hd)
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    att = jax.nn.softmax(s, axis=-1)
    att = jnp.where(mask[:, None, :], att, 0.0)
    conv = jnp.einsum("nhp,nphf->nhf", att, val).reshape(n, k_dim)

    tag = lin(params["tag"][0], codes)
    h = codes
    denom = jnp.maximum(mask.sum(1, keepdims=True), 1).astype(dtype)
    for hop in range(1, model["tag_hops"] + 1):
        h = (h[src] * mask[..., None]).sum(1) / denom
        tag = tag + lin(params["tag"][hop], h)

    agg = jax.nn.selu(0.5 * (conv + tag))
    agg = jax.nn.selu(lin(params["out"], agg) + lin(params["root"], codes))
    logit = mlp(params["f1"], agg - codes)[:, 0]
    return {"anomaly_prob": jax.nn.sigmoid(logit.astype(jnp.float32)),
            "codes": codes.astype(jnp.float32)}


def score(params, graphs: Dict[str, np.ndarray], feats: Features,
          model: dict, dtype: str = "float32",
          precision: Optional[str] = "highest"):
    """Forward pass over a stack of equal-sized graphs (leading axis)."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(jax.vmap(
        lambda p, g: forward(p, g, feats, model, jnp.dtype(dtype)),
        in_axes=(None, 0)))
    with jax.default_matmul_precision(precision):
        out = fn(params, {k: jnp.asarray(v) for k, v in graphs.items()})
    return {k: np.asarray(v) for k, v in out.items()}
