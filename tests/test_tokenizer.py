"""Byte tokenizer, HF tokenizer adapter, UTF-8-safe streaming detokenizer,
chat template."""

import json

import pytest

from finchat_tpu.io.schemas import ChatMessage
from finchat_tpu.models.tokenizer import (
    ByteTokenizer,
    HFTokenizer,
    IncrementalDecoder,
    get_tokenizer,
    render_chat,
)


def test_byte_roundtrip():
    tok = ByteTokenizer()
    text = "Penny saves $1,500/mo — 良い 🎉"
    assert tok.decode(tok.encode(text)) == text


def test_bos_prepend():
    tok = ByteTokenizer()
    ids = tok.encode("a", add_bos=True)
    assert ids[0] == tok.bos_id and ids[1:] == [ord("a")]


def test_incremental_decoder_never_tears_multibyte():
    tok = ByteTokenizer()
    text = "héllo 🎉 良"
    ids = tok.encode(text)
    dec = IncrementalDecoder(tok)
    out = ""
    for t in ids:
        piece = dec.push(t)
        assert "�" not in piece
        out += piece
    out += dec.flush()
    assert out == text


def test_incremental_decoder_ignores_specials():
    tok = ByteTokenizer()
    dec = IncrementalDecoder(tok)
    assert dec.push(tok.eos_id) == ""
    assert dec.push(ord("x")) == "x"


def test_incremental_decoder_garbage_does_not_stall():
    tok = ByteTokenizer()
    dec = IncrementalDecoder(tok)
    # 0xFF is never valid UTF-8; a run of them must flush as replacements
    out = "".join(dec.push(0xFF) for _ in range(6))
    assert "�" in out  # emitted, not buffered forever


# --- HFTokenizer over a locally-built tokenizer dir (no network) -----------


@pytest.fixture(scope="module")
def hf_tokenizer_dir(tmp_path_factory):
    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    path = tmp_path_factory.mktemp("hf_tok")
    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=320,
        special_tokens=["<s>", "</s>", "<pad>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    tok.train_from_iterator(
        ["hello world", "what did I spend on groceries?",
         "retrieve_transactions", '{"search_query": "recent"}', "🎉 良い"],
        trainer,
    )
    tok.save(str(path / "tokenizer.json"))
    (path / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "bos_token": "<s>", "eos_token": "</s>", "pad_token": "<pad>",
    }))
    return path


def test_hf_tokenizer_roundtrip_and_specials(hf_tokenizer_dir):
    pytest.importorskip("transformers")
    tok = HFTokenizer(str(hf_tokenizer_dir))
    assert tok.vocab_size > 0
    assert tok.bos_id != tok.eos_id
    text = "what did I spend on groceries?"
    ids = tok.encode(text)
    assert tok.decode(ids) == text
    with_bos = tok.encode(text, add_bos=True)
    assert with_bos[0] == tok.bos_id and with_bos[1:] == ids


def test_get_tokenizer_dispatch(hf_tokenizer_dir):
    pytest.importorskip("transformers")
    assert isinstance(get_tokenizer(""), ByteTokenizer)
    assert isinstance(get_tokenizer(str(hf_tokenizer_dir)), HFTokenizer)


def test_incremental_decoder_hf_path(hf_tokenizer_dir):
    """The HF branch of IncrementalDecoder: multibyte text split across
    byte-fallback pieces streams without mojibake."""
    pytest.importorskip("transformers")
    tok = HFTokenizer(str(hf_tokenizer_dir))
    text = "hello 🎉 良い world"
    ids = tok.encode(text)
    dec = IncrementalDecoder(tok)
    out = ""
    for t in ids:
        piece = dec.push(t)
        assert "�" not in piece
        out += piece
    out += dec.flush()
    assert out == text


# --- the two readers of one directory (PR 54): ``tokenizers`` where there is
# a ``tokenizer.json``, ``AutoTokenizer`` where there is none ----------------

PARITY_TEXTS = (
    "what did I spend on groceries?",
    "Penny saves $1,500/mo — 良い 🎉 , is n't it ? I 'm sure , it 's fine .",
    "  two  spaces\tand a tab\nand a line ",
    "",
    "<s> named </s> specials <pad> [CLS] in [SEP] the text",
)


def _wordpiece_dir(path):
    """A BERT-style directory as sentence-transformers saves bge's: WordPiece,
    ``[CLS] $A [SEP]``, truncation and padding set IN the file, two names
    written as dicts with ``content``."""
    from tokenizers import Tokenizer, decoders, models, normalizers, pre_tokenizers, processors

    words = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]", "what", "did", "i", "spend",
             "on", "groceries", "?", ".", ",", "'", "s", "m", "n", "t", "it", "is", "fine",
             "sure", "##s", "##ing", "save", "penny", "$", "1", "500", "/", "mo", "two",
             "spaces", "and", "a", "tab", "line", "named", "specials", "in", "the", "text",
             "<", ">", "pad", "良", "##い"]
    tok = Tokenizer(models.WordPiece(vocab={w: i for i, w in enumerate(words)},
                                     unk_token="[UNK]"))
    tok.normalizer = normalizers.BertNormalizer(lowercase=True)
    tok.pre_tokenizer = pre_tokenizers.BertPreTokenizer()
    tok.decoder = decoders.WordPiece(prefix="##", cleanup=False)
    tok.post_processor = processors.TemplateProcessing(
        single="[CLS] $A [SEP]", pair="[CLS] $A [SEP] $B:1 [SEP]:1",
        special_tokens=[("[CLS]", 2), ("[SEP]", 3)])
    tok.add_special_tokens(["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"])
    tok.enable_truncation(max_length=6)
    tok.enable_padding(length=6, pad_id=0, pad_token="[PAD]")
    path.mkdir(parents=True, exist_ok=True)
    tok.save(str(path / "tokenizer.json"))
    (path / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "BertTokenizer", "do_lower_case": True,
        "cls_token": {"content": "[CLS]", "lstrip": False, "normalized": False,
                      "rstrip": False, "single_word": False},
        "sep_token": "[SEP]", "unk_token": "[UNK]", "mask_token": "[MASK]"}))
    (path / "special_tokens_map.json").write_text(json.dumps({
        "pad_token": {"content": "[PAD]", "lstrip": False, "normalized": False,
                      "rstrip": False, "single_word": False},
        "cls_token": "[CLS]", "sep_token": "[SEP]", "unk_token": "[UNK]"}))
    return path


def _metaspace_dir(path):
    """A Llama-2-style directory: BPE over '▁' pieces with byte fallback, the
    decoder that strips the leading space, ``<s> $A``, ``LlamaTokenizer``."""
    from tokenizers import Tokenizer, decoders, models, normalizers, processors

    pieces = ["<unk>", "<s>", "</s>"] + [f"<0x{b:02X}>" for b in range(256)] + [
        "▁", "▁what", "▁did", "▁I", "▁spend", "▁on", "▁groceries", "?", "▁No", "▁tool",
        "▁call", "▁is", "▁it", "s", "n", "'", "t", "▁,", "▁.", "▁two", "▁spaces", "▁a"]
    tok = Tokenizer(models.BPE(vocab={p: i for i, p in enumerate(pieces)}, merges=[],
                               unk_token="<unk>", byte_fallback=True, fuse_unk=True))
    tok.normalizer = normalizers.Sequence(
        [normalizers.Prepend("▁"), normalizers.Replace(" ", "▁")])
    tok.decoder = decoders.Sequence([decoders.Replace("▁", " "), decoders.ByteFallback(),
                                     decoders.Fuse(), decoders.Strip(" ", 1, 0)])
    tok.post_processor = processors.TemplateProcessing(
        single="<s> $A", pair="<s> $A <s> $B", special_tokens=[("<s>", 1)])
    tok.add_special_tokens(["<unk>", "<s>", "</s>"])
    path.mkdir(parents=True, exist_ok=True)
    tok.save(str(path / "tokenizer.json"))
    (path / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "LlamaTokenizer", "bos_token": "<s>", "eos_token": "</s>",
        "unk_token": "<unk>", "legacy": False, "add_bos_token": True, "add_eos_token": False}))
    return path


@pytest.fixture(scope="module")
def parity_dirs(hf_tokenizer_dir, tmp_path_factory):
    from perfbench.synth_tokenizer import write_tokenizer_dir

    root = tmp_path_factory.mktemp("parity")
    return {"bpe": hf_tokenizer_dir,
            "synth32768": write_tokenizer_dir(root / "synth32768", 32768),
            "synth200064": write_tokenizer_dir(root / "synth200064", 200064),
            "wordpiece": _wordpiece_dir(root / "wordpiece"),
            "metaspace": _metaspace_dir(root / "metaspace")}


@pytest.mark.parametrize("clean_up", [True, False, None], ids=["clean", "raw", "absent"])
@pytest.mark.parametrize("kind", ["bpe", "synth32768", "synth200064", "wordpiece", "metaspace"])
def test_tokenizers_reader_matches_autotokenizer(parity_dirs, tmp_path, monkeypatch,
                                                 kind, clean_up):
    """Id for id and character for character: what ``tokenizers`` reads from a
    ``tokenizer.json`` is what ``AutoTokenizer`` made of the same directory."""
    transformers = pytest.importorskip("transformers")
    import shutil

    from finchat_tpu.models import tokenizer as tokmod

    path = tmp_path / kind
    shutil.copytree(parity_dirs[kind], path)
    config = json.loads((path / "tokenizer_config.json").read_text())
    config.pop("clean_up_tokenization_spaces", None)
    if clean_up is not None:
        config["clean_up_tokenization_spaces"] = clean_up
    (path / "tokenizer_config.json").write_text(json.dumps(config))

    ours = HFTokenizer(str(path))
    assert isinstance(ours._tok, tokmod._TokenizersBackend)
    monkeypatch.setattr(tokmod, "_hf_backend", lambda p: transformers.AutoTokenizer
                        .from_pretrained(p, local_files_only=True))
    theirs = HFTokenizer(str(path))
    assert isinstance(theirs._tok, transformers.PreTrainedTokenizerBase)

    for field in ("vocab_size", "bos_id", "eos_id", "pad_id", "eot_id"):
        assert getattr(ours, field) == getattr(theirs, field), field
    streams = []
    for text in PARITY_TEXTS:
        ids = theirs.encode(text)
        framed = theirs.encode_with_specials(text)
        assert ours.encode(text) == ids
        assert ours.encode(text, add_bos=True) == theirs.encode(text, add_bos=True)
        assert ours.encode_with_specials(text) == framed
        for seq in (ids, framed, framed + [ours.pad_id, ours.eos_id, ours.bos_id]):
            assert ours.decode(seq) == theirs.decode(seq)
        streams.append(framed)
    if kind == "wordpiece":  # the file's truncation (6) and padding are off
        assert len(ours.encode_with_specials(PARITY_TEXTS[1])) > 6
        assert ours.encode_with_specials("it")[0] == 2 and ours.encode_with_specials("it")[-1] == 3
    # the grammar's per-token texts ('▁foo' is ' foo', a named special is nothing)
    from finchat_tpu.agent.constrained import token_texts

    assert token_texts(ours) == token_texts(theirs)
    # every id decodes alike, and a stream over emoji and CJK flushes alike
    top = list(range(0, ours.vocab_size, max(1, ours.vocab_size // 997)))
    assert ours.decode(top) == theirs.decode(top)
    for ids in streams + [ours.encode("héllo 🎉 良い world 👩‍👩‍👧 終")]:
        pieces = []
        for tok in (ours, theirs):
            dec = IncrementalDecoder(tok)
            pieces.append([dec.push(t) for t in ids] + [dec.flush()])
        assert pieces[0] == pieces[1]


@pytest.mark.parametrize("kind,key,value,text", [
    ("metaspace", "add_eos_token", True, "what did I spend"),
    ("metaspace", "add_bos_token", False, "what did I spend"),
    ("wordpiece", "do_lower_case", False, "What did I spend"),
    ("wordpiece", "strip_accents", False, "penny savés"),
    ("bpe", "add_prefix_space", True, "hello world"),
])
def test_a_config_that_overrules_the_file_is_left_to_autotokenizer(parity_dirs, tmp_path,
                                                                   kind, key, value, text):
    """``LlamaTokenizerFast`` rebuilds the post-processor from ``add_bos_token``
    / ``add_eos_token``, ``BertTokenizerFast`` the normalizer, every fast
    tokenizer the pre-tokenizer's ``add_prefix_space``: where the config says
    other than the file, ``tokenizers`` alone would frame or split otherwise
    (the ids feed bge's encoder), so the directory goes to ``AutoTokenizer``."""
    transformers = pytest.importorskip("transformers")
    import shutil

    from finchat_tpu.models import tokenizer as tokmod

    path = tmp_path / kind
    shutil.copytree(parity_dirs[kind], path)
    config = json.loads((path / "tokenizer_config.json").read_text())
    assert tokmod._TokenizersBackend(path).overlaid == []
    (path / "tokenizer_config.json").write_text(json.dumps(config | {key: value}))

    file_alone = tokmod._TokenizersBackend(path)
    assert file_alone.overlaid == [key]
    ours = HFTokenizer(str(path))
    assert isinstance(ours._tok, transformers.PreTrainedTokenizerBase)
    theirs = transformers.AutoTokenizer.from_pretrained(str(path), local_files_only=True)
    framed = theirs.encode(text, add_special_tokens=True)
    assert ours.encode_with_specials(text) == framed
    assert file_alone.encode(text, add_special_tokens=True) != framed


def test_a_tokenizer_json_is_read_without_transformers(parity_dirs):
    """The served path never calls a deep-learning framework: building the
    adapter over a ``tokenizer.json`` imports neither it nor its wrapper."""
    import subprocess
    import sys

    code = ("import sys; from finchat_tpu.models.tokenizer import get_tokenizer; "
            f"t = get_tokenizer({str(parity_dirs['synth32768'])!r}); "
            "assert t.decode(t.encode('ok')) == 'ok' and t.vocab_size == 32768; "
            "print([m for m in ('transformers', 'torch') if m in sys.modules])")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=300, check=True)
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_a_directory_without_tokenizer_json_takes_autotokenizer(tmp_path):
    transformers = pytest.importorskip("transformers")
    words = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]", "hello", "world", "##s"]
    (tmp_path / "vocab.txt").write_text("\n".join(words) + "\n")
    (tmp_path / "tokenizer_config.json").write_text(json.dumps(
        {"tokenizer_class": "BertTokenizer", "do_lower_case": True}))
    tok = HFTokenizer(str(tmp_path))
    assert isinstance(tok._tok, transformers.PreTrainedTokenizerBase)
    assert tok.vocab_size == len(words) and tok.pad_id == 0
    assert tok.encode_with_specials("hello worlds") == [2, 5, 6, 7, 3]
    assert tok.decode([2, 5, 6, 7, 3]) == "hello worlds"


def test_render_chat_structure():
    history = [
        ChatMessage(sender="UserMessage", message="hi"),
        ChatMessage(sender="AIMessage", message="hello!"),
    ]
    prompt = render_chat("SYSTEM RULES", "MY CONTEXT", history, "what now?")
    # system block contains system_prompt then context (llm_agent.py:47-51)
    assert prompt.index("SYSTEM RULES") < prompt.index("MY CONTEXT")
    assert prompt.index("MY CONTEXT") < prompt.index("hi")
    assert prompt.index("hi") < prompt.index("hello!") < prompt.index("what now?")
    assert prompt.rstrip().endswith("<|assistant|>")
