"""A CNN configuration file, read into the layers the benchmark counts and
checks, the program's CNNConfig and the seeded weights.

A configuration is a JSON file under bench/configs/, written from the
published layer table.  `layers()` unrolls it into a flat list of layers
with their sizes; the work counts, the plain reference (bench/reference.py)
and the weight maker all walk that one list, so nothing here reads the
program's graph, kernel operand shapes or HLO.

Block kinds (the published ones of the two papers and the program's stage
kinds they map onto):

  bottleneck -- ResNet v1.5: 1x1 (width) relu, 3x3 (width, the block's
                stride) relu, 1x1 (out); a 1x1 projection of the input, at
                the block's stride, when its channels or size change; add,
                relu.  The program's "bottleneck" stage with out // 4 = width.
  inverted   -- MobileNetV2: 1x1 expansion to expand * in channels relu6,
                3x3 depthwise (the block's stride) relu6, 1x1 projection;
                add the input when stride 1 and channels equal.
  conv       -- a plain k x k conv with its activation.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"
# Mean of every conv channel after the folded batch norm (make_params).
BN_BETA = 1.0


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    kind: str                      # conv | dw | add | maxpool | gap | fc
    inputs: Tuple[str, ...]        # producing layers; "input" is the image
    hw_in: int
    hw_out: int
    cin: int
    cout: int
    k: int = 1
    stride: int = 1
    padding: str = "SAME"
    act: str = "none"              # none | relu | relu6
    w: Tuple = ()                  # path of the weight in the param tree
    b: Tuple = ()                  # path of the bias

    @property
    def macs(self) -> int:
        """Multiply-accumulates for one image."""
        px = self.hw_out * self.hw_out
        if self.kind == "conv":
            return px * self.k * self.k * self.cin * self.cout
        if self.kind == "dw":
            return px * self.k * self.k * self.cout
        if self.kind == "fc":
            return self.cin * self.cout
        return 0

    @property
    def weight_bytes(self) -> int:
        """int8 weights (biases left out)."""
        if self.kind == "conv":
            return self.k * self.k * self.cin * self.cout
        if self.kind == "dw":
            return self.k * self.k * self.cout
        if self.kind == "fc":
            return self.cin * self.cout
        return 0

    @property
    def map_bytes(self) -> int:
        """int8 input maps plus int8 output map for one image."""
        ins = len(self.inputs) * self.hw_in * self.hw_in * self.cin
        if self.kind == "fc":
            return self.cin + self.cout
        return ins + self.hw_out * self.hw_out * self.cout

    @property
    def params(self) -> int:
        return self.weight_bytes + (self.cout if self.w else 0)


def config_names() -> List[str]:
    return sorted(p.stem for p in CONFIG_DIR.glob("*.json"))


def load_config(name: str) -> dict:
    path = CONFIG_DIR / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no configuration {name!r} under {CONFIG_DIR} "
                       f"(have {config_names()})")
    cfg = json.loads(path.read_text())
    if cfg["name"] != name:
        raise ValueError(f"{path} names itself {cfg['name']!r}")
    return cfg


def _out_hw(hw: int, k: int, s: int, padding: str) -> int:
    if padding == "SAME":
        return -(-hw // s)
    return (hw - k) // s + 1


def layers(cfg: dict) -> List[Layer]:
    """The configuration unrolled into layers, in execution order."""
    out: List[Layer] = []
    pad = cfg.get("padding", "SAME")

    def add(**kw) -> Layer:
        kw.setdefault("padding", pad)
        layer = Layer(**kw)
        out.append(layer)
        return layer

    def conv(name, x: Layer, cout, k, s, act, w, b) -> Layer:
        return add(name=name, kind="conv", inputs=(x.name,), hw_in=x.hw_out,
                   hw_out=_out_hw(x.hw_out, k, s, pad), cin=x.cout,
                   cout=cout, k=k, stride=s, act=act, w=w, b=b)

    inp = cfg["input"]
    x = Layer(name="input", kind="input", inputs=(), hw_in=inp["size"],
              hw_out=inp["size"], cin=inp["channels"], cout=inp["channels"])
    st = cfg["stem"]
    x = conv("stem", x, st["out"], st["kernel"], st["stride"], st["act"],
             ("stem_w",), ("stem_b",))
    stage = 0
    if "maxpool" in cfg:
        mp = cfg["maxpool"]
        x = add(name="maxpool", kind="maxpool", inputs=(x.name,),
                hw_in=x.hw_out,
                hw_out=_out_hw(x.hw_out, mp["kernel"], mp["stride"],
                               mp["padding"]),
                cin=x.cout, cout=x.cout, k=mp["kernel"], stride=mp["stride"],
                padding=mp["padding"])
        stage = 1
    for blk in cfg["blocks"]:
        for r in range(blk["repeat"]):
            s = blk["stride"] if r == 0 else 1
            p = ("stages", stage, r)
            nm = f"s{stage}.b{r}"
            if blk["kind"] == "bottleneck":
                if cfg.get("stride_at", "3x3") != "3x3":
                    raise ValueError("only v1.5 bottlenecks (stride in the "
                                     "3x3) map onto the program")
                if blk["width"] * 4 != blk["out"]:
                    raise ValueError("the program's bottleneck has width = "
                                     "out / 4")
                h = conv(nm + ".conv1", x, blk["width"], 1, 1, "relu",
                         p + ("w1",), p + ("b1",))
                h = conv(nm + ".conv2", h, blk["width"], 3, s, "relu",
                         p + ("w2",), p + ("b2",))
                h = conv(nm + ".conv3", h, blk["out"], 1, 1, "none",
                         p + ("w3",), p + ("b3",))
                skip = x
                if x.cout != blk["out"] or s != 1:
                    skip = conv(nm + ".proj", x, blk["out"], 1, s, "none",
                                p + ("wskip",), p + ("bskip",))
                x = add(name=nm + ".add", kind="add",
                        inputs=(h.name, skip.name), hw_in=h.hw_out,
                        hw_out=h.hw_out, cin=h.cout, cout=h.cout, act="relu")
            elif blk["kind"] == "inverted":
                mid = x.cout * blk["expand"]
                h = x
                if blk["expand"] != 1 or cfg.get("expand_conv_at_t1"):
                    h = conv(nm + ".expand", x, mid, 1, 1, "relu6",
                             p + ("we",), p + ("be",))
                h = add(name=nm + ".dw", kind="dw", inputs=(h.name,),
                        hw_in=h.hw_out, hw_out=_out_hw(h.hw_out, 3, s, pad),
                        cin=mid, cout=mid, k=3, stride=s, act="relu6",
                        w=p + ("wd",), b=p + ("bd",))
                h = conv(nm + ".project", h, blk["out"], 1, 1, "none",
                         p + ("wp",), p + ("bp",))
                if s == 1 and x.cout == blk["out"]:
                    h = add(name=nm + ".add", kind="add",
                            inputs=(h.name, x.name), hw_in=h.hw_out,
                            hw_out=h.hw_out, cin=h.cout, cout=h.cout)
                x = h
            elif blk["kind"] == "conv":
                x = conv(nm + ".conv", x, blk["out"], blk["kernel"], s,
                         blk["act"], p + ("w",), p + ("b",))
            else:
                raise ValueError(f"unknown block kind {blk['kind']!r}")
        stage += 1
    x = add(name="gap", kind="gap", inputs=(x.name,), hw_in=x.hw_out,
            hw_out=1, cin=x.cout, cout=x.cout)
    add(name="fc", kind="fc", inputs=(x.name,), hw_in=1, hw_out=1,
        cin=x.cout, cout=cfg["num_classes"], w=("head_w",), b=("head_b",))
    return out


def work(cfg: dict) -> Dict[str, float]:
    """Model work per image, summed over the layer list: MACs, ops
    (2 x MACs) and parameters."""
    ls = layers(cfg)
    macs = float(sum(l.macs for l in ls))
    return {"macs": macs, "ops": 2.0 * macs,
            "params": float(sum(l.params for l in ls))}


def roofline_s_per_image(cfg: dict, wave: int, peak_ops: float,
                         peak_bytes: float) -> float:
    """Least device time for one image of a wave: per layer the larger of
    ops over peak ops and bytes over bandwidth, weights read once a wave."""
    t = 0.0
    for l in layers(cfg):
        ops = 2.0 * l.macs
        nbytes = l.map_bytes + l.weight_bytes / wave
        t += max(ops / peak_ops, nbytes / peak_bytes)
    return t


def program_config(cfg: dict):
    """The program's CNNConfig for this configuration."""
    from repro.core.config import CNNConfig, ConvSpec

    stages = []
    if "maxpool" in cfg:
        mp = cfg["maxpool"]
        if mp["padding"] != "VALID":
            raise ValueError("the program's max pool is VALID")
        stages.append(ConvSpec("pool", kernel=mp["kernel"],
                               stride=mp["stride"]))
    for blk in cfg["blocks"]:
        if blk["kind"] == "bottleneck":
            stages.append(ConvSpec("bottleneck", out_ch=blk["out"], kernel=3,
                                   stride=blk["stride"],
                                   repeat=blk["repeat"]))
        elif blk["kind"] == "inverted":
            if blk["expand"] == 1 and not cfg.get("expand_conv_at_t1"):
                raise ValueError("the program's inverted block always has "
                                 "an expansion conv")
            stages.append(ConvSpec("inverted", out_ch=blk["out"], kernel=3,
                                   stride=blk["stride"], repeat=blk["repeat"],
                                   expand=blk["expand"]))
        else:
            if blk["act"] != "relu":
                raise ValueError("the program's conv stage applies relu")
            stages.append(ConvSpec("conv", out_ch=blk["out"],
                                   kernel=blk["kernel"], stride=blk["stride"],
                                   repeat=blk["repeat"]))
    st = cfg["stem"]
    if st["act"] != "relu" or cfg.get("padding", "SAME") != "SAME":
        raise ValueError("the program's stem is a SAME conv with relu")
    return CNNConfig(name=cfg["name"], input_hw=cfg["input"]["size"],
                     input_ch=cfg["input"]["channels"],
                     stem_kernel=st["kernel"], stem_stride=st["stride"],
                     stem_ch=st["out"], stages=tuple(stages),
                     num_classes=cfg["num_classes"])


def _param_shapes(cfg: dict) -> Dict[Tuple, Tuple[Tuple[int, ...], str]]:
    """Weight and bias paths with their shapes and init kinds."""
    shapes = {}
    for l in layers(cfg):
        if not l.w:
            continue
        if l.kind == "conv":
            shapes[l.w] = ((l.k, l.k, l.cin, l.cout), "he")
        elif l.kind == "dw":
            shapes[l.w] = ((l.k, l.k, l.cout), "he")
        else:
            shapes[l.w] = ((l.cin, l.cout), "fc")
        shapes[l.b] = ((l.cout,), "bias")
    return shapes


def key_for(seed: int, stream: int):
    """A JAX PRNG key from any whole-number seed (all of its bits)."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make_params(cfg: dict, seed: int, init_images: int = 8):
    """The float32 weights, on the device, in one jitted call from the
    seed, as the program's param tree (stem_w/stem_b, stages[i][r]{...},
    head_w/head_b; a pool stage holds an empty block).

    Weights are He-normal (the classifier N(0, 1/fan_in)); then batch norm
    is folded in over `init_images` seeded images (bench/reference.py
    fold_batch_norm), every channel with mean BN_BETA and unit variance
    there.  Unfolded random weights give logits that barely depend on the
    image, so that one answer for every image would pass the check; with
    beta 0 the network is chaotic and int8 rounding alone decorrelates the
    answers; beta 1 keeps most units in the linear part of ReLU, where int8
    moves an answer by about a third of the spread between images."""
    import jax
    import jax.numpy as jnp

    from bench.reference import fold_batch_norm

    ls = layers(cfg)
    shapes = _param_shapes(cfg)
    paths = sorted((p for p in shapes if shapes[p][1] != "bias"), key=str)
    size, ch = cfg["input"]["size"], cfg["input"]["channels"]

    def build(key):
        kw, kx = jax.random.split(key)
        keys = jax.random.split(kw, len(paths))
        raw = {}
        for k, path in zip(keys, paths):
            shape, kind = shapes[path]
            fan_in = int(np.prod(shape[:-1]))
            std = np.sqrt((2.0 if kind == "he" else 1.0) / fan_in)
            raw[path] = jax.random.normal(k, shape, jnp.float32) * \
                np.float32(std)
        x = jax.random.uniform(kx, (init_images, size, size, ch),
                               jnp.float32, -1.0, 1.0)
        return fold_batch_norm(ls, raw, x, BN_BETA)

    with jax.default_matmul_precision("highest"):
        flat = jax.jit(build)(key_for(seed, 0))
    n_stages = (1 if "maxpool" in cfg else 0) + len(cfg["blocks"])
    repeats = ([1] if "maxpool" in cfg else []) + [b["repeat"]
                                                  for b in cfg["blocks"]]
    tree: dict = {"stages": [[{} for _ in range(repeats[i])]
                             for i in range(n_stages)]}
    for path, v in flat.items():
        if path[0] == "stages":
            tree["stages"][path[1]][path[2]][path[3]] = v
        else:
            tree[path[0]] = v
    return tree


def get_param(tree, path):
    v = tree
    for k in path:
        v = v[k]
    return v
