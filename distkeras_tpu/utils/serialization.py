"""Model and parameter (de)serialization — pickle-free.

TPU-native equivalent of the reference's model wire format (reference:
distkeras/utils.py -> serialize_keras_model / deserialize_keras_model, which
ship a dict of {architecture-JSON, weight list} between driver and executors).
The reference pickles those dicts onto the socket; unpickling peer bytes is
arbitrary-code-execution on the receiving host, so this codec replaces it
with a non-executable encoding (VERDICT r1 weak #3 / next-step 6):

    frame   = MAGIC "DKT1" + 4-byte big-endian header length
            + JSON header + raw npz payload
    header  = {"tree": <structure node>} — a typed description of the pytree
              (dict / list / tuple / namedtuple / None nodes, leaf indices)
    payload = np.savez of the numeric leaves, loaded with allow_pickle=False

NamedTuple nodes (optax optimizer states) are encoded structurally by class
path + field names. On decode the class is re-imported ONLY when its module
root is on a small allowlist and the imported object really is a NamedTuple
class with the same fields; anything else degrades to an anonymous namedtuple
with the same fields — structurally equal for compute, never an arbitrary
constructor call.
"""

from __future__ import annotations

import collections
import importlib
import io
import json
import struct

import numpy as np

_MAGIC = b"DKT1"
_HLEN = struct.Struct(">I")

# Module roots we are willing to import while decoding a namedtuple node.
_NT_MODULE_ALLOWLIST = ("optax", "distkeras_tpu", "jax", "flax", "collections")


# ------------------------------------------------------------ structure codec


def _encode_node(obj, leaves: list) -> dict:
    from distkeras_tpu.ops.quantization import Int4Weight

    if obj is None:
        return {"t": "none"}
    if isinstance(obj, Int4Weight):
        # packed int4 weight (serving bundles): the two array children
        # ride the leaf stream like any other; the logical row count is
        # structural metadata
        return {
            "t": "int4",
            "rows": int(obj.rows),
            "children": [
                _encode_node(obj.q4, leaves),
                _encode_node(obj.s, leaves),
            ],
        }
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # namedtuple
        cls = type(obj)
        return {
            "t": "nt",
            "cls": f"{cls.__module__}:{cls.__qualname__}",
            "fields": list(obj._fields),
            "children": [_encode_node(c, leaves) for c in obj],
        }
    if isinstance(obj, dict):
        keys = list(obj.keys())
        if not all(isinstance(k, str) for k in keys):
            raise TypeError("only str-keyed dicts are serializable")
        return {
            "t": "dict",
            "keys": keys,
            "children": [_encode_node(obj[k], leaves) for k in keys],
        }
    if isinstance(obj, (list, tuple)):
        return {
            "t": "list" if isinstance(obj, list) else "tuple",
            "children": [_encode_node(c, leaves) for c in obj],
        }
    arr = np.asarray(obj)
    if arr.dtype.name == "bfloat16":
        # NumPy's own formats know no bfloat16: the 16 bits ride as
        # uint16 (a view, no copy) and the node names the dtype
        leaves.append(arr.view(np.uint16))
        return {"t": "leaf", "i": len(leaves) - 1, "dt": "bfloat16"}
    if arr.dtype.kind not in "biufc":
        raise TypeError(f"non-numeric leaf of dtype {arr.dtype} is not serializable")
    leaves.append(arr)
    return {"t": "leaf", "i": len(leaves) - 1}


def _resolve_namedtuple(path: str, fields: list):
    """Import the namedtuple class at ``module:qualname`` if (and only if)
    it is allowlisted and structurally matches; else build an anonymous
    stand-in with the same fields."""
    mod_name, _, qual = str(path).partition(":")
    if mod_name.split(".")[0] in _NT_MODULE_ALLOWLIST:
        try:
            obj = importlib.import_module(mod_name)
            for part in qual.split("."):
                obj = getattr(obj, part)
            if (
                isinstance(obj, type)
                and issubclass(obj, tuple)
                and getattr(obj, "_fields", None) == tuple(fields)
            ):
                return obj
        except Exception:
            pass
    name = qual.rsplit(".", 1)[-1] or "AnonymousState"
    if not name.isidentifier():
        name = "AnonymousState"
    return collections.namedtuple(name, fields, rename=True)


def _decode_node(node: dict, leaves: list):
    kind = node["t"]
    if kind == "none":
        return None
    if kind == "leaf":
        leaf = leaves[node["i"]]
        if node.get("dt") == "bfloat16":
            import ml_dtypes

            leaf = leaf.view(ml_dtypes.bfloat16)
        return leaf
    children = [_decode_node(c, leaves) for c in node["children"]]
    if kind == "int4":
        from distkeras_tpu.ops.quantization import Int4Weight

        return Int4Weight(children[0], children[1], int(node["rows"]))
    if kind == "dict":
        return dict(zip(node["keys"], children))
    if kind == "list":
        return children
    if kind == "tuple":
        return tuple(children)
    if kind == "nt":
        cls = _resolve_namedtuple(node["cls"], list(node["fields"]))
        return cls(*children)
    raise ValueError(f"unknown structure node type {kind!r}")


# -------------------------------------------------------------------- framing


def pack_frame(header: dict, blob: bytes = b"") -> bytes:
    """JSON header + raw binary payload in one length-framed buffer."""
    h = json.dumps(header).encode()
    return _MAGIC + _HLEN.pack(len(h)) + h + blob


def unpack_frame(data: bytes) -> tuple[dict, bytes]:
    if data[: len(_MAGIC)] != _MAGIC:
        raise ValueError("bad frame: missing DKT1 magic (refusing legacy pickle)")
    off = len(_MAGIC)
    (hlen,) = _HLEN.unpack_from(data, off)
    off += _HLEN.size
    header = json.loads(data[off : off + hlen].decode())
    return header, data[off + hlen :]


# ----------------------------------------------------------------- public API


class _Window:
    """The rest of a seekable file from its current position on, as a file
    of its own whose position 0 is there: what ``np.savez`` / ``np.load``
    are handed so that the zip's offsets do not depend on the frame
    headers written before it, and a multi-gigabyte bundle streams to and
    from disk a leaf at a time instead of through ``bytes`` copies."""

    def __init__(self, f):
        self._f, self._base = f, f.tell()

    def tell(self):
        return self._f.tell() - self._base

    def seek(self, off, whence=0):
        self._f.seek(off + self._base if whence == 0 else off, whence)
        return self.tell()

    def read(self, n=-1):
        return self._f.read(n)

    def write(self, b):
        return self._f.write(b)

    def flush(self):
        self._f.flush()

    def seekable(self):
        return True

    def readable(self):
        return True

    def writable(self):
        return True

    def close(self):  # the owner closes the real file
        pass


def _write_frame_header(f, header: dict) -> None:
    f.write(pack_frame(header))


def _read_frame_header(f) -> dict:
    head = f.read(len(_MAGIC) + _HLEN.size)
    if head[: len(_MAGIC)] != _MAGIC:
        raise ValueError("bad frame: missing DKT1 magic (refusing legacy pickle)")
    (hlen,) = _HLEN.unpack_from(head, len(_MAGIC))
    return json.loads(f.read(hlen).decode())


def _write_params(f, params) -> None:
    """The params frame (structure header + npz) written to ``f`` from its
    current position on, a leaf at a time."""
    leaves: list = []
    tree = _encode_node(params, leaves)
    _write_frame_header(f, {"tree": tree})
    np.savez(_Window(f), **{f"a{i}": leaf for i, leaf in enumerate(leaves)})


def _read_params(f):
    header = _read_frame_header(f)
    with np.load(_Window(f), allow_pickle=False) as z:
        leaves = [z[f"a{i}"] for i in range(len(z.files))]
    return _decode_node(header["tree"], leaves)


def serialize_params(params) -> bytes:
    """Pytree of arrays -> bytes (typed structure header + npz, no pickle)."""
    buf = io.BytesIO()
    _write_params(buf, params)
    return buf.getvalue()


def deserialize_params(blob: bytes):
    return _read_params(io.BytesIO(blob))


def serialize_model(model) -> bytes:
    """Sequential model -> bytes: architecture spec JSON + weight arrays."""
    from distkeras_tpu.ops.quantization import count_quantized

    if count_quantized(getattr(model, "params", None) or {}):
        raise ValueError(
            "model holds an int8-quantized serving tree; quantization is a "
            "LOAD-TIME transform — serialize the f32 master and call "
            "ops.quantization.quantize_model after deserialize_model"
        )
    buf = io.BytesIO()
    np.savez(buf, *[np.asarray(w) for w in model.get_weights()])
    return pack_frame(
        {
            "spec": json.dumps(model.get_config()),
            "input_shape": list(model.input_shape),
        },
        buf.getvalue(),
    )


def deserialize_model(blob: bytes):
    from distkeras_tpu.models.sequential import Sequential

    header, payload = unpack_frame(blob)
    if header.get("serving"):
        raise ValueError(
            "this frame is a quantized SERVING bundle, not an f32 "
            "model — load it with deserialize_serving_bundle / "
            "load_serving_bundle"
        )
    model = Sequential.from_config(json.loads(header["spec"]))
    model.build(tuple(header["input_shape"]))
    with np.load(io.BytesIO(payload), allow_pickle=False) as z:
        model.set_weights([z[k] for k in z.files])
    return model


def save_params(path: str, params) -> None:
    with open(path, "wb") as f:
        f.write(serialize_params(params))


def load_params(path: str):
    with open(path, "rb") as f:
        return deserialize_params(f.read())


# ------------------------------------------------------------ serving bundles


def _write_serving_bundle(f, model) -> None:
    from distkeras_tpu.ops.quantization import is_serving_tree

    if getattr(model, "params", None) is None:
        raise ValueError("serving bundle needs a BUILT model")
    if not is_serving_tree(model.params):
        raise ValueError(
            "model is not quantized — a serving bundle stores the "
            "quantized tree (ops.quantization.quantize_model first); "
            "for the f32 master use serialize_model"
        )
    _write_frame_header(f, {
        "spec": json.dumps(model.get_config()),
        "input_shape": list(model.input_shape),
        "serving": True,
    })
    _write_params(f, model.params)


def serialize_serving_bundle(model) -> bytes:
    """Quantized model -> bytes, the DELIBERATE counterpart of
    ``serialize_model``'s quantized-tree rejection: that guard stops a
    lossy tree being saved AS the training master by accident; this
    format exists so serving hosts don't ship 4-8x the weight bytes and
    re-quantize on every boot. The frame carries the architecture spec
    plus the quantized params tree (int8 dicts ride the leaf stream
    natively; ``Int4Weight`` has a structural node). Loads serve-only:
    trainers and ``serialize_model`` reject the result, exactly as they
    reject any quantized tree. A tree cast to bfloat16
    (``quantize_model(bits=16)``) is a serving tree too."""
    buf = io.BytesIO()
    _write_serving_bundle(buf, model)
    return buf.getvalue()


def deserialize_serving_bundle(blob: bytes):
    """bytes -> a serve-only model: architecture rebuilt from the spec,
    params replaced by the stored quantized tree (validated structurally
    against the spec-built model — same tree paths, quantized leaves'
    logical shapes matching the f32 ones they replace)."""
    return _read_serving_bundle(io.BytesIO(blob))


def _build_shapes(model, input_shape):
    """The spec-built model's params as shapes and dtypes: ``build`` under
    ``jax.eval_shape``, so that a model of billions of parameters is not
    initialised (in float32, on the device) only to be checked against and
    thrown away. A model whose layers carry state (BatchNorm's moving
    statistics, which a bundle does not hold) is built for real."""
    import jax

    shapes, state = jax.eval_shape(
        lambda: (model.build(input_shape).params, model.state)
    )
    if jax.tree_util.tree_leaves(state):
        return model.build(input_shape).params
    model.state = state  # no leaves: plain containers, no tracer inside
    return shapes


def _read_serving_bundle(f):
    from distkeras_tpu.models.sequential import Sequential
    from distkeras_tpu.ops.quantization import is_quantized, qshape

    header = _read_frame_header(f)
    if not header.get("serving"):
        raise ValueError(
            "not a serving bundle (use deserialize_model for f32 frames)"
        )
    model = Sequential.from_config(json.loads(header["spec"]))
    built = _build_shapes(model, tuple(header["input_shape"]))
    loaded = _read_params(f)

    def check(path, built, got):
        if is_quantized(got):
            # validate the quantized leaf's INTERNALS, not just its
            # logical shape: a truncated q4 or a broadcastable (1,)
            # scale would otherwise load cleanly and serve garbage
            # (qshape trusts Int4Weight.rows; broadcasting hides a
            # wrong-length s until the predictions are silently wrong)
            from distkeras_tpu.ops.quantization import Int4Weight

            want = tuple(built.shape)
            if len(want) != 2:
                # quantization only ever replaces 2-D matmul weights; a
                # "quantized" leaf standing in for a bias/LN gain is a
                # crafted payload and must fail as a ValueError, not an
                # IndexError on want[1] below
                raise ValueError(
                    f"serving bundle structure mismatch at {path}: "
                    f"quantized leaf where the spec builds a "
                    f"{len(want)}-D array"
                )
            if tuple(qshape(got)) != want:
                raise ValueError(
                    f"serving bundle shape mismatch at {path}: "
                    f"spec builds {want}, bundle holds {tuple(qshape(got))}"
                )
            if isinstance(got, Int4Weight):
                q4_want = ((want[0] + 1) // 2, want[1])
                if (
                    tuple(np.shape(got.q4)) != q4_want
                    or tuple(np.shape(got.s)) != (want[1],)
                    or np.asarray(got.q4).dtype != np.int8
                    or np.asarray(got.s).dtype != np.float32
                ):
                    raise ValueError(
                        f"serving bundle int4 internals mismatch at "
                        f"{path}: q4 {tuple(np.shape(got.q4))}/"
                        f"{np.asarray(got.q4).dtype} vs {q4_want}/int8, "
                        f"s {tuple(np.shape(got.s))}/"
                        f"{np.asarray(got.s).dtype} vs ({want[1]},)/f32"
                    )
            # int8: qshape already IS q.shape, so only the scale vector
            # and the dtypes need their own checks (a broadcastable (1,)
            # scale serves silently wrong numbers; an int32 "q" — or an
            # int32 q4 above, whose nibble sign-extension returns the
            # whole packed byte — decodes to garbage with no error)
            elif (
                tuple(np.shape(got["s"])) != (want[1],)
                or np.asarray(got["q"]).dtype != np.int8
                or np.asarray(got["s"]).dtype != np.float32
            ):
                raise ValueError(
                    f"serving bundle int8 internals mismatch at {path}: "
                    f"q dtype {np.asarray(got['q']).dtype} vs int8, "
                    f"s {tuple(np.shape(got['s']))}/"
                    f"{np.asarray(got['s']).dtype} vs ({want[1]},)/f32"
                )
            return
        if isinstance(built, dict) != isinstance(got, dict) or (
            isinstance(built, dict) and set(built) != set(got)
        ):
            raise ValueError(
                f"serving bundle structure mismatch at {path}"
            )
        if isinstance(built, dict):
            for k in built:
                check(f"{path}/{k}", built[k], got[k])
        elif isinstance(built, (list, tuple)):
            if len(built) != len(got):
                raise ValueError(
                    f"serving bundle structure mismatch at {path}"
                )
            for i, (b, g) in enumerate(zip(built, got)):
                check(f"{path}[{i}]", b, g)
        elif tuple(built.shape) != np.shape(got):
            raise ValueError(
                f"serving bundle shape mismatch at {path}: "
                f"{tuple(built.shape)} vs {np.shape(got)}"
            )
        elif built.dtype != np.asarray(got).dtype and not (
            built.dtype == np.float32
            and np.asarray(got).dtype.name == "bfloat16"
        ):
            # shape alone would let a crafted bundle substitute e.g. a
            # float64 or int array for an f32 bias/LN gain and serve it
            # silently; non-quantized leaves must match the spec-built
            # dtype exactly (the quantized branch pins its own dtypes),
            # but for the 16-bit serving cast: bfloat16 where the spec
            # builds float32 (``quantize_model(bits=16)``)
            raise ValueError(
                f"serving bundle dtype mismatch at {path}: spec builds "
                f"{built.dtype}, bundle holds "
                f"{np.asarray(got).dtype}"
            )

    check("params", built, loaded)
    model.params = loaded
    return model


def save_serving_bundle(path: str, model) -> None:
    """Streams to the file a leaf at a time: a bundle may be larger than
    what the host could hold twice."""
    with open(path, "wb") as f:
        _write_serving_bundle(f, model)


def load_serving_bundle(path: str):
    with open(path, "rb") as f:
        return _read_serving_bundle(f)
