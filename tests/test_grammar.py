"""Regex-constrained decoding: the byte-regex engine (differential vs
`re`), the token-level lift, and end-to-end constrained generation
through the paged server — plain, mixed-batch, speculative, preempted,
and over HTTP with the OpenAI json_object response_format."""

import json
import re

import jax
import numpy as np
import pytest

from cloud_server_tpu.config import InferConfig, ModelConfig
from cloud_server_tpu.data.tokenizer import ByteTokenizer
from cloud_server_tpu.inference import grammar
from cloud_server_tpu.inference.paged_server import PagedInferenceServer
from cloud_server_tpu.inference.sampling import SamplingParams
from cloud_server_tpu.models import transformer

TOK = ByteTokenizer()
CFG = ModelConfig(
    vocab_size=300, embed_dim=32, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=8, mlp_dim=64, max_seq_len=256, dtype="float32",
    param_dtype="float32", remat="none")
EOS = TOK.eos_id
ICFG = InferConfig(max_decode_len=16, temperature=0.0, eos_token_id=EOS,
                   pad_token_id=0)
SRV_KW = dict(max_slots=4, max_context=128, page_size=8, prefill_chunk=16,
              prompt_buckets=[16, 32], tokenizer=TOK)


@pytest.fixture(scope="module")
def params():
    return transformer.init_params(CFG, jax.random.key(0))


# ---------------------------------------------------------------------------
# byte-regex engine vs python re (fullmatch)
# ---------------------------------------------------------------------------

DIFF_PATTERNS = [
    r"[0-9]+", r"-?[0-9]+(\.[0-9]+)?", r"(abc|de)*f", r"a{2,4}", r"a{3}",
    r"a{2,}", r"\w+@\w+\.(com|org)", r"[^x]+", r"(yes|no)",
    r'"[a-z ]*"', r"\d{4}-\d{2}-\d{2}", r"(?:ab)+", r"x?y?z?",
    r"[\x41-\x43]+",
]


@pytest.mark.parametrize("pattern", DIFF_PATTERNS)
def test_byte_dfa_matches_re(pattern):
    dfa = grammar.compile_byte_dfa(pattern)
    cre = re.compile(pattern.encode())
    rng = np.random.default_rng(0)
    alphabet = np.frombuffer(b'abcdefxyz0123456789.-@_" ABC', np.uint8)
    for _ in range(400):
        s = bytes(rng.choice(alphabet, size=rng.integers(0, 11)))
        assert dfa.matches(s) == (cre.fullmatch(s) is not None), (pattern,
                                                                  s)


def test_json_regex_accepts_and_rejects():
    jd = grammar.compile_byte_dfa(grammar.json_object_regex(2))
    good = ['{"a": 1}', '{}', '{"x": true, "y": -3.5e2}',
            '{"a": [1, 2, "x"], "b": {"c": null}}', '{"a": "b\\nc"}',
            '{"a": "\\u00e9"}']
    bad = ['{', '[1]', '{"a": 01}', '{"a" 1}', '{a: 1}', '']
    for doc in good:
        assert jd.matches(doc.encode()), doc
    for doc in bad:
        assert not jd.matches(doc.encode()), doc


ADDRESS_SCHEMA = {
    "type": "object",
    "properties": {
        "name": {"type": "string"},
        "age": {"type": "integer"},
        "tags": {"type": "array", "items": {"type": "string"},
                 "maxItems": 3},
        "kind": {"enum": ["a", "b", 3]},
        "nested": {"type": "object",
                   "properties": {"ok": {"type": "boolean"}},
                   "required": ["ok"]},
    },
    "required": ["name", "age", "kind", "nested"],
}


def _schema_dfa(schema, **kw):
    return grammar.compile_byte_dfa(grammar.json_schema_regex(schema,
                                                              **kw))


def test_json_schema_regex_accepts_valid():
    dfa = _schema_dfa(ADDRESS_SCHEMA)
    good = [
        '{"name": "x", "age": 3, "kind": "a", "nested": {"ok": true}}',
        '{"name":"", "age":-7, "tags":["t"], "kind":3,'
        ' "nested":{"ok":false}}',
        '{"name": "q", "age": 0, "tags": [], "kind": "b",'
        ' "nested": {"ok": true}}',
    ]
    for doc in good:
        assert dfa.matches(doc.encode()), doc
        json.loads(doc)  # sanity: truly valid JSON


def test_json_schema_regex_rejects_invalid():
    dfa = _schema_dfa(ADDRESS_SCHEMA)
    bad = [
        '{"name": "x", "age": 3, "kind": "a"}',            # missing req
        '{"age": 3, "name": "x", "kind": "a",'
        ' "nested": {"ok": true}}',                        # wrong order
        '{"name": "x", "age": 3.5, "kind": "a",'
        ' "nested": {"ok": true}}',                        # float age
        '{"name": "x", "age": 3, "kind": "c",'
        ' "nested": {"ok": true}}',                        # bad enum
        '{"name": "x", "age": 3, "kind": "a",'
        ' "nested": {"ok": true}, "extra": 1}',            # closed world
        '{"name": "x", "age": 3,'
        ' "tags": ["a", "b", "c", "d"], "kind": "a",'
        ' "nested": {"ok": true}}',                        # > maxItems
    ]
    for doc in bad:
        assert not dfa.matches(doc.encode()), doc


def test_json_schema_optional_combinations():
    schema = {"type": "object",
              "properties": {"a": {"type": "integer"},
                             "b": {"type": "integer"},
                             "c": {"type": "integer"}},
              "required": ["b"]}
    dfa = _schema_dfa(schema)
    assert dfa.matches(b'{"b": 1}')
    assert dfa.matches(b'{"a": 1, "b": 2}')
    assert dfa.matches(b'{"b": 1, "c": 2}')
    assert dfa.matches(b'{"a": 1, "b": 2, "c": 3}')
    assert not dfa.matches(b'{"a": 1}')          # missing required
    assert not dfa.matches(b'{"b": 1, "a": 2}')  # order violated


def test_json_schema_scalar_features():
    assert _schema_dfa({"type": "string", "minLength": 2,
                        "maxLength": 4}).matches(b'"abc"')
    assert not _schema_dfa({"type": "string", "minLength": 2}
                           ).matches(b'"a"')
    # bare "items" implies array, symmetric with bare "properties"
    arr = _schema_dfa({"items": {"type": "integer"}})
    assert arr.matches(b"[1, 2]") and not arr.matches(b"3")
    dfa = _schema_dfa({"anyOf": [{"type": "integer"},
                                 {"type": "null"}]})
    assert dfa.matches(b"42") and dfa.matches(b"null")
    assert not dfa.matches(b'"x"')
    assert _schema_dfa({"const": {"k": [1, "s"]}}).matches(
        b'{"k":[1,"s"]}')
    # string enum with regex metacharacters must be escaped
    assert _schema_dfa({"enum": ["a+b", "c[d]"]}).matches(b'"a+b"')


def test_json_schema_errors():
    with pytest.raises(ValueError):  # unsupported keyword is loud
        grammar.json_schema_regex({"type": "integer", "minimum": 3})
    with pytest.raises(ValueError):  # nesting past max_depth
        grammar.json_schema_regex(
            {"type": "object", "properties": {
                "a": {"type": "object", "properties": {
                    "b": {"type": "integer"}}}}}, max_depth=1)
    with pytest.raises(ValueError):  # too many optionals
        grammar.json_schema_regex(
            {"type": "object",
             "properties": {f"k{i}": {"type": "integer"}
                            for i in range(8)}})
    with pytest.raises(ValueError):  # required key not declared
        grammar.json_schema_regex(
            {"type": "object", "properties": {}, "required": ["x"]})
    with pytest.raises(ValueError, match="maxLength"):  # loud, named
        grammar.json_schema_regex({"type": "string", "maxLength": 300})
    with pytest.raises(ValueError, match="minItems"):
        grammar.json_schema_regex({"type": "array", "minItems": 400})

    # combinatorial blow-up: optional keys double the regex per key and
    # compound across nesting — must trip the size cap bottom-up (cheap
    # failure, bounded memory), not OOM building a multi-GB string
    def nest(d):
        props = {f"k{i}": ({"type": "integer"} if d == 0 else
                           nest(d - 1)) for i in range(6)}
        return {"type": "object", "properties": props}  # all optional
    with pytest.raises(ValueError, match="regex over"):
        grammar.json_schema_regex(nest(3), max_depth=8)


def test_regex_errors():
    for pat in ["(", "a{3,2}", "[z-a]", "a{", "*a", "[]"]:
        with pytest.raises(ValueError):
            grammar.compile_byte_dfa(pat)


def test_token_dfa_lift_byte_tokenizer():
    """Token-level table agrees with the byte DFA byte-for-byte, and
    unspellable ids (specials) are always DEAD."""
    tb = grammar.token_bytes(TOK, CFG.vocab_size)
    tdfa = grammar.compile_token_dfa(r"[ab]+c", tb)
    bdfa = grammar.compile_byte_dfa(r"[ab]+c")
    for s in [b"abc", b"c", b"aab", b"aabc"]:
        toks = list(s)
        assert (tdfa.walk(toks) != grammar.DEAD
                and bool(tdfa.accept[tdfa.walk(toks)])) == bdfa.matches(s)
    assert (tdfa.next_state[:, TOK.eos_id] == grammar.DEAD).all()
    assert (tdfa.next_state[:, 299] == grammar.DEAD).all()  # out of tok


def test_token_bytes_specials_from_declaration(tmp_path):
    """Specials come from the tokenizer's DECLARED added-token flags,
    not a string-shape heuristic: real vocab entries spelled '<div>' or
    '[]' stay spellable under a grammar; declared specials never are."""
    pytest.importorskip("tokenizers")
    from tokenizers import Tokenizer, models, trainers
    from cloud_server_tpu.data.tokenizer import HFTokenizer
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    trainer = trainers.BpeTrainer(
        vocab_size=300, special_tokens=["<unk>", "<s>", "</s>"])
    tok.train_from_iterator(["div class abc 0123"] * 20, trainer)
    tok.add_tokens(["<div>", "[]"])  # plain added tokens, NOT special
    path = tmp_path / "tokenizer.json"
    tok.save(str(path))
    hf = HFTokenizer(str(path))
    tb = grammar.token_bytes(hf, hf.vocab_size)
    assert tb[tok.token_to_id("<div>")] == b"<div>"
    assert tb[tok.token_to_id("[]")] == b"[]"
    for name in ("<s>", "</s>", "<unk>"):
        assert tb[tok.token_to_id(name)] is None
    # no declared pad -> wrapper falls back to eos; real vocab id 0
    # (here '<unk>'-adjacent base ids) must NOT be banned by fallback
    assert hf.pad_is_declared is False


def test_token_bytes_sentencepiece_byte_fallback(tmp_path):
    """With the FULL '<0x00>'..'<0xFF>' convention present, fallback
    tokens decode to their raw byte — not their literal spelling (which
    would let a grammar emit bytes that violate the constraint)."""
    pytest.importorskip("tokenizers")
    from tokenizers import Tokenizer, models, trainers
    from cloud_server_tpu.data.tokenizer import HFTokenizer
    tok = Tokenizer(models.BPE(unk_token="<unk>"))
    trainer = trainers.BpeTrainer(
        vocab_size=300, special_tokens=["<unk>", "<s>", "</s>"])
    tok.train_from_iterator(["plain words here"] * 20, trainer)
    tok.add_tokens([f"<0x{b:02X}>" for b in range(256)])
    path = tmp_path / "tokenizer.json"
    tok.save(str(path))
    hf = HFTokenizer(str(path))
    tb = grammar.token_bytes(hf, hf.vocab_size)
    assert tb[tok.token_to_id("<0x0A>")] == b"\n"
    assert tb[tok.token_to_id("<0xFF>")] == b"\xff"


# ---------------------------------------------------------------------------
# constrained generation through the paged server
# ---------------------------------------------------------------------------


def _valid(pattern: str, toks: list[int]) -> bool:
    return re.fullmatch(pattern, TOK.decode(toks)) is not None


@pytest.mark.parametrize("spec_drafts", [0, 2])
def test_constrained_generation_matches_pattern(params, spec_drafts):
    """Whatever the (random) model wants, the output must fullmatch the
    pattern and finish via EOS at an accepting state."""
    pattern = r"[0-9]{2,6}"
    srv = PagedInferenceServer(params, CFG, ICFG,
                               spec_drafts=spec_drafts, **SRV_KW)
    reqs = [srv.submit(TOK.encode(p), max_new_tokens=16,
                       sampling=SamplingParams(regex=pattern))
            for p in ("hello", "42", "x")]
    srv.run_until_idle()
    for r in reqs:
        toks = r.result()
        assert _valid(pattern, toks), TOK.decode(toks)
        assert r.finish_reason == "eos"


def test_constrained_spec_parity_greedy(params):
    """Greedy constrained generation is identical with and without
    in-server speculation (the window walk must mask position by
    position exactly)."""
    pattern = r'"[a-z]+"'
    plain = PagedInferenceServer(params, CFG, ICFG, **SRV_KW)
    spec = PagedInferenceServer(params, CFG, ICFG, spec_drafts=3,
                                **SRV_KW)
    for prompt in ("say", "q"):
        a = plain.submit(TOK.encode(prompt), max_new_tokens=12,
                         sampling=SamplingParams(regex=pattern))
        b = spec.submit(TOK.encode(prompt), max_new_tokens=12,
                        sampling=SamplingParams(regex=pattern))
        plain.run_until_idle()
        spec.run_until_idle()
        assert a.result() == b.result(), prompt


def test_mixed_constrained_and_free_batch(params):
    """A constrained row must not disturb an unconstrained greedy row
    sharing the batch."""
    free_ref = PagedInferenceServer(params, CFG, ICFG, **SRV_KW)
    want = free_ref.generate([TOK.encode("hello")], max_new_tokens=8)[0]
    srv = PagedInferenceServer(params, CFG, ICFG, **SRV_KW)
    free = srv.submit(TOK.encode("hello"), max_new_tokens=8)
    con = srv.submit(TOK.encode("n:"), max_new_tokens=8,
                     sampling=SamplingParams(regex=r"[0-9]+"))
    srv.run_until_idle()
    assert free.result() == want
    assert _valid(r"[0-9]+", con.result())


def test_a_constrained_row_keeps_the_launch_behind_the_commit(params):
    """The scheduler puts a dispatch on the device's queue
    ahead of the commit before it only where the plan has no constrained
    row (`_launch_waits`): with one, the dispatch waits, its flight
    record says why, and the free rows around it go ahead again once
    the constrained request is gone."""
    srv = PagedInferenceServer(params, CFG, ICFG, decode_chunk=1, **SRV_KW)
    free = srv.submit(TOK.encode("hello"), max_new_tokens=24)
    con = srv.submit(TOK.encode("n:"), max_new_tokens=6,
                     sampling=SamplingParams(regex=r"[0-9]+"))
    srv.run_until_idle()
    assert _valid(r"[0-9]+", con.result()) and free.done
    ov = [r for r in srv.flight_window() if r.get("overlap")]
    waits = [r.get("launch_waits") for r in ov]
    assert "grammar" in waits and None in waits
    assert set(waits) <= {"fill", "grammar", None}
    for r in ov:
        assert r["launch_ahead"] == ("launch_waits" not in r)
    # while the constrained row lived no dispatch went ahead
    first_ahead = waits.index(None)
    assert "grammar" not in waits[first_ahead:]


def test_two_patterns_share_server(params):
    srv = PagedInferenceServer(params, CFG, ICFG, **SRV_KW)
    a = srv.submit(TOK.encode("a"), max_new_tokens=10,
                   sampling=SamplingParams(regex=r"[0-9]+"))
    b = srv.submit(TOK.encode("b"), max_new_tokens=10,
                   sampling=SamplingParams(regex=r"(yes|no)"))
    srv.run_until_idle()
    assert _valid(r"[0-9]+", a.result())
    assert TOK.decode(b.result()) in ("yes", "no")


def test_constrained_survives_preemption(params):
    """Preempted constrained requests resume mid-pattern (the DFA state
    is replayed from the committed tokens at re-admission)."""
    kw = dict(SRV_KW)
    kw.update(max_slots=4, num_pages=10)
    srv = PagedInferenceServer(params, CFG, ICFG, **kw)
    con = srv.submit(TOK.encode("zz"), max_new_tokens=12,
                     sampling=SamplingParams(regex=r"[0-9]{8,10}"))
    crowd = [srv.submit(TOK.encode("crowd" * 3), max_new_tokens=12)
             for _ in range(3)]
    srv.run_until_idle()
    del crowd
    assert _valid(r"[0-9]{8,10}", con.result())


def test_slot_reuse_after_constrained_is_clean(params):
    """A constrained request that finishes via EOS leaves its slot's
    device DFA state DEAD (the EOS column is DEAD and DEAD is sticky).
    An UNCONSTRAINED request later admitted into that slot through a
    grammar-free admission group must not inherit it — even while
    another live slot is constrained (regression: the stale DEAD row
    masked every token for the reused slot, committing garbage)."""
    ref = PagedInferenceServer(params, CFG, ICFG, **SRV_KW)
    want = ref.generate([TOK.encode("hello")], max_new_tokens=8)[0]
    srv = PagedInferenceServer(params, CFG, ICFG, **SRV_KW)
    # [0-9]{2}: after two digits EOS is the ONLY allowed token, so the
    # greedy finish is via EOS and the slot's gstate lands on DEAD
    con = srv.submit(TOK.encode("n:"), max_new_tokens=8,
                     sampling=SamplingParams(regex=r"[0-9]{2}"))
    srv.run_until_idle()
    assert con.finish_reason == "eos"  # precondition: DEAD was written
    free = srv.submit(TOK.encode("hello"), max_new_tokens=8)
    while srv._jobs or srv.num_pending:  # admit via a grammar-free group
        srv.step()
    con2 = srv.submit(TOK.encode("m:"), max_new_tokens=8,
                      sampling=SamplingParams(regex=r"[0-9]{2}"))
    srv.run_until_idle()
    assert free.result() == want
    assert _valid(r"[0-9]{2}", con2.result())


def test_constrained_validation(params):
    srv = PagedInferenceServer(params, CFG, ICFG, **SRV_KW)
    with pytest.raises(ValueError):  # bad pattern -> client-side error
        srv.submit([1], sampling=SamplingParams(regex="("))
    no_tok = PagedInferenceServer(params, CFG, ICFG,
                                  **{**SRV_KW, "tokenizer": None})
    with pytest.raises(ValueError):
        no_tok.submit([1], sampling=SamplingParams(regex="[0-9]+"))
    no_eos = PagedInferenceServer(
        params, CFG, InferConfig(max_decode_len=8, temperature=0.0,
                                 eos_token_id=-1, pad_token_id=0),
        **SRV_KW)
    with pytest.raises(ValueError):
        no_eos.submit([1], sampling=SamplingParams(regex="[0-9]+"))


def test_sampled_constrained_generation(params):
    """Temperature sampling under a constraint still yields a valid
    match (masking composes with the stochastic path)."""
    srv = PagedInferenceServer(params, CFG, ICFG, **SRV_KW)
    r = srv.submit(TOK.encode("x"), max_new_tokens=12,
                   sampling=SamplingParams(regex=r"[ab]{3,8}",
                                           temperature=1.5, seed=3))
    srv.run_until_idle()
    assert _valid(r"[ab]{3,8}", r.result())


@pytest.mark.parametrize("spec_drafts", [0, 2])
def test_schema_constrained_generation(params, spec_drafts):
    """Generations under a compiled JSON Schema validate against it —
    under sampling AND speculation. Completion (finish 'eos') implies
    the document parses and satisfies the schema."""
    schema = {"type": "object",
              "properties": {"n": {"type": "integer"},
                             "k": {"enum": ["x", "y"]}},
              "required": ["n", "k"]}
    pattern = grammar.json_schema_regex(schema)
    srv = PagedInferenceServer(params, CFG, ICFG,
                               spec_drafts=spec_drafts, **SRV_KW)
    reqs = [srv.submit(TOK.encode(p), max_new_tokens=60,
                       sampling=SamplingParams(regex=pattern,
                                               temperature=0.9,
                                               seed=5))
            for p in ("give json", "x")]
    srv.run_until_idle()
    for r in reqs:
        text = TOK.decode(r.result())
        if r.finish_reason == "eos":
            doc = json.loads(text)
            assert isinstance(doc["n"], int) and doc["k"] in ("x", "y")
            assert list(doc) == ["n", "k"]
        else:
            assert r.finish_reason == "length"


def test_json_schema_over_http(params):
    """OpenAI response_format json_schema end-to-end."""
    from urllib import request as urq
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, ICFG, **SRV_KW).start()
    front = HttpFrontend(srv, tokenizer=TOK).start()
    try:
        host, port = front.address
        body = json.dumps({
            "prompt": "data:", "max_tokens": 60,
            "response_format": {
                "type": "json_schema",
                "json_schema": {"name": "point", "schema": {
                    "type": "object",
                    "properties": {"x": {"type": "integer"},
                                   "y": {"type": "integer"}},
                    "required": ["x", "y"]}}}}).encode()
        req = urq.Request(f"http://{host}:{port}/v1/completions",
                          data=body)
        with urq.urlopen(req, timeout=300) as resp:
            out = json.loads(resp.read())
        choice = out["choices"][0]
        if choice["finish_reason"] == "stop":
            doc = json.loads(choice["text"])
            assert isinstance(doc["x"], int) and isinstance(doc["y"], int)
        else:
            assert choice["finish_reason"] == "length"
        # a bad schema is a 400, not a handler crash
        bad = json.dumps({
            "prompt": "p", "response_format": {
                "type": "json_schema",
                "json_schema": {"schema": {"type": "integer",
                                           "minimum": 1}}}}).encode()
        import urllib.error as uerr
        with pytest.raises(uerr.HTTPError) as ei:
            urq.urlopen(urq.Request(
                f"http://{host}:{port}/v1/completions", data=bad),
                timeout=60)
        assert ei.value.code == 400
    finally:
        front.stop()
        srv.stop()


def test_json_mode_over_http(params):
    """OpenAI response_format json_object through the HTTP front-end
    produces parseable flat JSON."""
    from urllib import request as urq
    from cloud_server_tpu.inference.http_server import HttpFrontend
    srv = PagedInferenceServer(params, CFG, ICFG, **SRV_KW).start()
    front = HttpFrontend(srv, tokenizer=TOK).start()
    try:
        host, port = front.address
        body = json.dumps({
            "prompt": "give me json", "max_tokens": 60,
            "response_format": {"type": "json_object"}}).encode()
        req = urq.Request(f"http://{host}:{port}/v1/completions",
                          data=body)
        with urq.urlopen(req, timeout=300) as resp:
            out = json.loads(resp.read())
        choice = out["choices"][0]
        if choice["finish_reason"] == "stop":  # completed the grammar
            parsed = json.loads(choice["text"])
            assert isinstance(parsed, dict)
        else:  # ran out of budget mid-pattern: still a valid prefix
            assert choice["finish_reason"] == "length"
    finally:
        front.stop()
        srv.stop()
