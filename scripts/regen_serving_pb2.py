#!/usr/bin/env python
"""Regenerate a committed *_pb2.py module from its .proto — WITHOUT
protoc (the serving image does not ship it; Makefile `proto` stays the
canonical path on machines that do).

This is a deliberately small compiler for the subset of proto3 the
project's contracts use: messages with scalar / repeated / message /
map<scalar,scalar> fields and reserved numbers, and services with
unary or server-streaming methods. It parses the .proto into a FileDescriptorProto, serializes it
(byte-identical to protoc's output for this subset — field descriptors
carry name/number/label/type in field-number order and no json_name,
exactly like protoc), and emits the same generated-module shape the
committed pb2 files use, including the pure-python `_serialized_start/
_end` offset table (computed by locating each descriptor's serialized
bytes inside the file blob, which is how the offsets are defined).

  python scripts/regen_serving_pb2.py          # rewrite serving_pb2.py
  python scripts/regen_serving_pb2.py --check  # verify pb2 matches proto
                                               # (exit 1 on drift)

--check is wired into the observability test suite so a proto edit that
forgets the regeneration step is a red tier-1 test, not a runtime
ServingStatsResponse(**stats) TypeError three layers away.
"""

from __future__ import annotations

import ast
import re
import sys

from google.protobuf import descriptor_pb2 as dpb

REPO = __import__("os").path.dirname(
    __import__("os").path.dirname(__import__("os").path.abspath(__file__))
)
PROTO_PATH = f"{REPO}/protos/serving.proto"
PB2_PATH = f"{REPO}/ggrmcp_tpu/rpc/pb/serving_pb2.py"

F = dpb.FieldDescriptorProto
_SCALARS = {
    "double": F.TYPE_DOUBLE,
    "float": F.TYPE_FLOAT,
    "int64": F.TYPE_INT64,
    "uint64": F.TYPE_UINT64,
    "int32": F.TYPE_INT32,
    "bool": F.TYPE_BOOL,
    "string": F.TYPE_STRING,
    "bytes": F.TYPE_BYTES,
    "uint32": F.TYPE_UINT32,
    "sint32": F.TYPE_SINT32,
    "sint64": F.TYPE_SINT64,
    "fixed32": F.TYPE_FIXED32,
    "fixed64": F.TYPE_FIXED64,
}

_FIELD_RE = re.compile(
    r"^(repeated\s+)?(map<\s*(\w+)\s*,\s*(\w+)\s*>|[\w.]+)\s+(\w+)\s*=\s*(\d+)\s*;"
)
_RPC_RE = re.compile(
    r"^rpc\s+(\w+)\s*\(\s*(stream\s+)?([\w.]+)\s*\)\s*"
    r"returns\s*\(\s*(stream\s+)?([\w.]+)\s*\)\s*;"
)


def _strip_comments(text: str) -> str:
    return re.sub(r"//[^\n]*", "", text)


def _camel(snake: str) -> str:
    return "".join(part.title() for part in snake.split("_"))


def parse_proto(text: str, name: str = "serving.proto") -> dpb.FileDescriptorProto:
    """Parse the supported proto3 subset into a FileDescriptorProto."""
    fdp = dpb.FileDescriptorProto(name=name, syntax="proto3")
    # One statement-ish token stream: blocks delimited by braces.
    lines = _strip_comments(text)
    pos = 0
    package = ""

    def err(msg: str) -> "SystemExit":
        return SystemExit(f"regen_serving_pb2: {msg}")

    # tokenize into top-level statements / blocks
    def find_block_end(start: int) -> int:
        depth = 0
        for i in range(start, len(lines)):
            if lines[i] == "{":
                depth += 1
            elif lines[i] == "}":
                depth -= 1
                if depth == 0:
                    return i
        raise err("unbalanced braces")

    while pos < len(lines):
        m = re.compile(r"\s*(syntax|package|message|service)\b").match(lines, pos)
        if m is None:
            if lines[pos:].strip():
                raise err(f"unsupported statement at: {lines[pos:pos+60]!r}")
            break
        kind = m.group(1)
        if kind == "syntax":
            semi = lines.index(";", m.end())
            if '"proto3"' not in lines[m.end():semi]:
                raise err("only proto3 is supported")
            pos = semi + 1
        elif kind == "package":
            semi = lines.index(";", m.end())
            package = lines[m.end():semi].strip()
            fdp.package = package
            pos = semi + 1
        else:
            name_m = re.compile(r"\s*(\w+)\s*\{").match(lines, m.end())
            if name_m is None:
                raise err(f"bad {kind} header near {lines[m.end():m.end()+40]!r}")
            brace = name_m.end() - 1
            end = find_block_end(brace)
            body = lines[name_m.end():end]
            if kind == "message":
                fdp.message_type.append(
                    _parse_message(name_m.group(1), body, package, err)
                )
            else:
                fdp.service.append(
                    _parse_service(name_m.group(1), body, package, err)
                )
            pos = end + 1
    return fdp


def _type_ref(type_name: str, package: str) -> str:
    return f".{package}.{type_name}" if "." not in type_name else f".{type_name}"


def _parse_message(name, body, package, err) -> dpb.DescriptorProto:
    msg = dpb.DescriptorProto(name=name)
    for stmt in body.split(";"):
        stmt = stmt.strip()
        if not stmt:
            continue
        if stmt.startswith("reserved "):
            # `reserved 14 to 20, 23;` -> [start, end) ranges, as protoc
            # writes them (field names are not supported, nor needed).
            for part in stmt[len("reserved "):].split(","):
                lo, _, hi = part.partition(" to ")
                msg.reserved_range.add(
                    start=int(lo), end=int(hi or lo) + 1
                )
            continue
        m = _FIELD_RE.match(stmt + ";")
        if m is None:
            raise err(f"unsupported field in {name}: {stmt!r}")
        repeated, type_tok, map_k, map_v, fname, num = m.groups()
        field = msg.field.add(name=fname, number=int(num))
        if type_tok.startswith("map<"):
            # protoc lowers map<K,V> to a repeated nested ...Entry
            # message with map_entry=true and key/value fields 1/2.
            entry = msg.nested_type.add(name=f"{_camel(fname)}Entry")
            entry.options.map_entry = True
            entry.field.add(
                name="key", number=1, label=F.LABEL_OPTIONAL,
                type=_SCALARS[map_k],
            )
            entry.field.add(
                name="value", number=2, label=F.LABEL_OPTIONAL,
                type=_SCALARS[map_v],
            )
            field.label = F.LABEL_REPEATED
            field.type = F.TYPE_MESSAGE
            field.type_name = f".{package}.{name}.{entry.name}"
        else:
            field.label = F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL
            if type_tok in _SCALARS:
                field.type = _SCALARS[type_tok]
            else:
                field.type = F.TYPE_MESSAGE
                field.type_name = _type_ref(type_tok, package)
    return msg


def _parse_service(name, body, package, err) -> dpb.ServiceDescriptorProto:
    svc = dpb.ServiceDescriptorProto(name=name)
    for stmt in body.split(";"):
        stmt = stmt.strip()
        if not stmt:
            continue
        m = _RPC_RE.match(stmt + ";")
        if m is None:
            raise err(f"unsupported rpc in {name}: {stmt!r}")
        rpc_name, c_stream, in_t, s_stream, out_t = m.groups()
        method = svc.method.add(
            name=rpc_name,
            input_type=_type_ref(in_t, package),
            output_type=_type_ref(out_t, package),
        )
        if c_stream:
            method.client_streaming = True
        if s_stream:
            method.server_streaming = True
    return svc


# ---------------------------------------------------------------------------
# module generation
# ---------------------------------------------------------------------------


def _offsets(fdp: dpb.FileDescriptorProto, blob: bytes) -> list[tuple[str, int, int, bytes]]:
    """(_MANGLED_NAME, start, end, serialized_options) per descriptor,
    in the committed pb2 ordering (messages with their nested entries,
    then services). start/end index the descriptor's serialized content
    inside the file blob — the offsets the pure-python runtime uses."""
    out = []
    cursor = 0

    def locate(content: bytes, from_: int) -> tuple[int, int]:
        idx = blob.index(content, from_)
        return idx, idx + len(content)

    for msg in fdp.message_type:
        content = msg.SerializeToString(deterministic=True)
        start, end = locate(content, cursor)
        cursor = start + 1
        out.append((f"_{msg.name.upper()}", start, end, b""))
        for nested in msg.nested_type:
            n_content = nested.SerializeToString(deterministic=True)
            n_start, n_end = locate(n_content, start)
            opts = (
                nested.options.SerializeToString(deterministic=True)
                if nested.HasField("options") else b""
            )
            out.append(
                (f"_{msg.name.upper()}_{nested.name.upper()}", n_start, n_end, opts)
            )
    for svc in fdp.service:
        content = svc.SerializeToString(deterministic=True)
        start, end = locate(content, cursor)
        cursor = start + 1
        out.append((f"_{svc.name.upper()}", start, end, b""))
    return out


def gen_module(fdp: dpb.FileDescriptorProto) -> str:
    blob = fdp.SerializeToString(deterministic=True)
    lines = [
        "# -*- coding: utf-8 -*-",
        "# Generated by the protocol buffer compiler.  DO NOT EDIT!",
        f"# source: {fdp.name}",
        '"""Generated protocol buffer code."""',
        "from google.protobuf.internal import builder as _builder",
        "from google.protobuf import descriptor as _descriptor",
        "from google.protobuf import descriptor_pool as _descriptor_pool",
        "from google.protobuf import symbol_database as _symbol_database",
        "# @@protoc_insertion_point(imports)",
        "",
        "_sym_db = _symbol_database.Default()",
        "",
        "",
        "",
        "",
        f"DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile({blob!r})",
        "",
        "_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())",
        f"_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, "
        f"'{fdp.name.replace('.proto', '_pb2')}', globals())",
        "if _descriptor._USE_C_DESCRIPTORS == False:",
        "",
        "  DESCRIPTOR._options = None",
    ]
    offs = _offsets(fdp, blob)
    for name, _s, _e, opts in offs:
        if opts:
            lines.append(f"  {name}._options = None")
            lines.append(f"  {name}._serialized_options = {opts!r}")
    for name, s, e, _opts in offs:
        lines.append(f"  {name}._serialized_start={s}")
        lines.append(f"  {name}._serialized_end={e}")
    lines.append("# @@protoc_insertion_point(module_scope)")
    return "\n".join(lines) + "\n"


def committed_blob(pb2_source: str) -> bytes:
    """The serialized FileDescriptorProto inside a generated pb2 module,
    extracted textually (importing would collide with the live pool)."""
    m = re.search(r"AddSerializedFile\((b(?:'|\").*)\)\n", pb2_source)
    if m is None:
        raise SystemExit("regen_serving_pb2: no AddSerializedFile in pb2")
    return ast.literal_eval(m.group(1))


def check() -> int:
    with open(PROTO_PATH, encoding="utf-8") as fh:
        fdp = parse_proto(fh.read())
    with open(PB2_PATH, encoding="utf-8") as fh:
        existing = fh.read()
    want = fdp.SerializeToString(deterministic=True)
    have = committed_blob(existing)
    if want != have:
        print(
            "regen_serving_pb2: serving_pb2.py is stale vs serving.proto "
            f"({len(have)} vs {len(want)} descriptor bytes); rerun "
            "scripts/regen_serving_pb2.py",
            file=sys.stderr,
        )
        return 1
    return 0


def main() -> int:
    if "--check" in sys.argv:
        return check()
    with open(PROTO_PATH, encoding="utf-8") as fh:
        fdp = parse_proto(fh.read())
    module = gen_module(fdp)
    with open(PB2_PATH, "w", encoding="utf-8") as fh:
        fh.write(module)
    print(f"wrote {PB2_PATH} ({len(module)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
