"""Which YAML parser reads a plan's documents (ISSUE 32): `models/expand.py`
takes PyYAML's libyaml loader where the library was built with it and the
pure-Python one otherwise. Both feed the same Python resolver and constructor,
so what they return has to be equal document for document; these cases pin
that on every YAML file the repo ships and on the scalars and structures
Kubernetes manifests trip on, and pin the counter and the `load.parse` span
attributes that say which parser was engaged (ISSUE 37). CPU only, no chip."""

import glob
import json
import os

import pytest
import yaml

from opensim_tpu.models import expand
from opensim_tpu.obs import trace as tracing
from opensim_tpu.obs.metrics import RECORDER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_libyaml = pytest.mark.skipif(
    not getattr(yaml, "__with_libyaml__", False), reason="this PyYAML was built without libyaml"
)

YAML_FILES = sorted(
    os.path.relpath(p, REPO)
    for root in ("example", "tests", "benchmarks/testdata")
    for ext in ("yaml", "yml")
    for p in glob.glob(os.path.join(REPO, root, "**", f"*.{ext}"), recursive=True)
)


def both(load):
    """`load(Loader)` under each loader: its documents, or the class of the
    `yaml.YAMLError` it raised."""
    out = []
    for loader in (yaml.CSafeLoader, yaml.SafeLoader):
        try:
            out.append(load(loader))
        except yaml.YAMLError as e:
            out.append(type(e))
    return out


def test_the_repo_ships_yaml_files():
    assert len(YAML_FILES) >= 51


@needs_libyaml
@pytest.mark.parametrize("path", YAML_FILES)
def test_both_loaders_read_a_shipped_file_alike(path):
    def load(loader):
        with open(os.path.join(REPO, path)) as f:
            return list(yaml.load_all(f, Loader=loader))

    c, py = both(load)
    assert c == py
    if isinstance(c, list):  # same types too: 1 == 1.0 == True would pass `==`
        assert repr(c) == repr(py)


_LOCAL_STORAGE = json.dumps(
    {"vgs": [{"name": "yoda-pool", "capacity": "107374182400"}],
     "devices": [{"device": "/dev/vdc", "capacity": "214748364800", "mediaType": "hdd"}]}
)

#: name -> (text, the documents expected, or None where only agreement is asked)
DOCUMENTS = {
    "cpu_string": ('resources: {requests: {cpu: "1"}}\n', [{"resources": {"requests": {"cpu": "1"}}}]),
    "cpu_int": ("resources:\n  requests:\n    cpu: 1\n", [{"resources": {"requests": {"cpu": 1}}}]),
    "cpu_milli_and_memory": ("cpu: 250m\nmemory: 64Gi\n", [{"cpu": "250m", "memory": "64Gi"}]),
    "bool_yes_no_on_off": ("a: yes\nb: no\nc: on\nd: off\ne: True\nf: 'yes'\n",
                           [{"a": True, "b": False, "c": True, "d": False, "e": True, "f": "yes"}]),
    "bool_keys": ("on: push\ny: 1\nn: 2\n", [{True: "push", "y": 1, "n": 2}]),
    "octal_012": ("mode: 012\nnew: 0o14\n", [{"mode": 10, "new": "0o14"}]),
    "hex_0x10": ("v: 0x10\n", [{"v": 16}]),
    "float_1e3": ("a: 1e3\nb: 1.0e+3\nc: 1.5e3\nd: 1.5e-3\n", [{"a": "1e3", "b": 1000.0, "c": "1.5e3", "d": 0.0015}]),
    "float_leading_dot": ("v: .5\nw: -.inf\nx: .nan\n", None),
    "sexagesimal": ("t: 1:30\nport: 22:22\n", [{"t": 90, "port": 1342}]),
    "underscored_int": ("v: 1_000\n", [{"v": 1000}]),
    "null_tilde_and_empty": ("a: ~\nb:\nc: null\nd: ''\ne: Null\n",
                             [{"a": None, "b": None, "c": None, "d": "", "e": None}]),
    "iso_timestamp": ("creationTimestamp: 2021-03-04T05:06:07Z\nday: 2021-03-04\nquoted: '2021-03-04'\n", None),
    "anchor_and_alias": ("base: &b {cpu: 1, memory: 2Gi}\nother: *b\nlist: [*b, *b]\n",
                         [{"base": {"cpu": 1, "memory": "2Gi"}, "other": {"cpu": 1, "memory": "2Gi"},
                           "list": [{"cpu": 1, "memory": "2Gi"}] * 2}]),
    "merge_key": ("defaults: &d\n  cpu: 1\n  zone: a\npod:\n  <<: *d\n  zone: b\n",
                  [{"defaults": {"cpu": 1, "zone": "a"}, "pod": {"cpu": 1, "zone": "b"}}]),
    "merge_key_list": ("a: &a {x: 1}\nb: &b {y: 2}\nc:\n  <<: [*a, *b]\n  z: 3\n",
                       [{"a": {"x": 1}, "b": {"y": 2}, "c": {"x": 1, "y": 2, "z": 3}}]),
    "literal_block": ("script: |\n  #!/bin/sh\n  echo hi\n\n  exit 0\nkeep: |+\n  a\n\nstrip: |-\n  b\n",
                      [{"script": "#!/bin/sh\necho hi\n\nexit 0\n", "keep": "a\n\n", "strip": "b"}]),
    "folded_block": ("text: >\n  one\n  two\n\n  three\nnext: >-\n  a\n  b\n",
                     [{"text": "one two\nthree\n", "next": "a b"}]),
    "json_annotation_string": (
        "metadata:\n  annotations:\n    simon/node-local-storage: '" + _LOCAL_STORAGE + "'\n",
        [{"metadata": {"annotations": {"simon/node-local-storage": _LOCAL_STORAGE}}}]),
    "flow_one_line_as_the_generator_writes": (
        "---\n" + json.dumps({"apiVersion": "v1", "kind": "Node", "metadata": {"name": "n-1", "labels": {"disk": "ssd"}},
                              "status": {"allocatable": {"cpu": "64", "memory": "256Gi", "pods": "110"}}}) + "\n"
        "---\n" + json.dumps({"kind": "Node", "spec": {"unschedulable": False, "taints": [], "x": None, "f": 1.5}}) + "\n",
        [{"apiVersion": "v1", "kind": "Node", "metadata": {"name": "n-1", "labels": {"disk": "ssd"}},
          "status": {"allocatable": {"cpu": "64", "memory": "256Gi", "pods": "110"}}},
         {"kind": "Node", "spec": {"unschedulable": False, "taints": [], "x": None, "f": 1.5}}]),
    "byte_order_mark": ("\ufeffkind: Pod\nname: a\n", [{"kind": "Pod", "name": "a"}]),
    "crlf_line_ends": ("kind: Pod\r\nspec:\r\n  nodeName: n1\r\ntext: |\r\n  a\r\n  b\r\n",
                       [{"kind": "Pod", "spec": {"nodeName": "n1"}, "text": "a\nb\n"}]),
    "non_ascii_label_values": ("labels:\n  team: plattform-grün\n  地域: 東京\n  emoji: \"\\U0001F680 \U0001F680\"\n",
                               [{"labels": {"team": "plattform-grün", "地域": "東京", "emoji": "\U0001F680 \U0001F680"}}]),
    "escapes_in_double_quotes": ('a: "tab\\there\\nnew \\u00e9 \\x41"\nb: \'it\'\'s\'\n',
                                 [{"a": "tab\there\nnew é A", "b": "it's"}]),
    "list_document": ("- a\n- b\n", [["a", "b"]]),
    "scalar_document": ("just a string\n", ["just a string"]),
    "empty_document_between_markers": ("kind: A\n---\n---\nkind: B\n", [{"kind": "A"}, None, {"kind": "B"}]),
    "empty_stream": ("", []),
    "comments_only": ("# nothing here\n", []),
    "document_end_markers": ("kind: A\n...\n---\nkind: B\n...\n", [{"kind": "A"}, {"kind": "B"}]),
    "yaml_directive": ("%YAML 1.1\n---\nkind: A\n", [{"kind": "A"}]),
    "duplicate_keys_last_wins": ("a: 1\na: 2\n", [{"a": 2}]),
    "multiline_plain_and_quoted_keys": ('"quoted key": plain\n  continued\n? complex\n: value\n',
                                        [{"quoted key": "plain continued", "complex": "value"}]),
    "binary_and_set_tags": ("b: !!binary aGVsbG8=\ns: !!set {a, b}\ni: !!int '7'\nt: !!str 7\n",
                            [{"b": b"hello", "s": {"a", "b"}, "i": 7, "t": "7"}]),
    "helm_value_is_not_yaml": ("metadata:\n  name: {{ .Release.Name }}\n", yaml.constructor.ConstructorError),
    "helm_suffixed_value_is_not_yaml": ("metadata:\n  name: {{ .Release.Name }}-web\n", yaml.parser.ParserError),
    "helm_block_is_not_yaml": ("{{- if .Values.on }}\nkind: A\n{{- end }}\n", yaml.parser.ParserError),
    "tab_indentation": ("a:\n\tb: 1\n", yaml.scanner.ScannerError),
    "unclosed_flow": ("a: [1, 2\n", yaml.parser.ParserError),
    "undefined_alias": ("a: *nowhere\n", yaml.composer.ComposerError),
    "python_object_tag_is_refused": ("a: !!python/object/apply:os.system ['true']\n",
                                     yaml.constructor.ConstructorError),
    "unknown_local_tag_is_refused": ("a: !mine 1\n", yaml.constructor.ConstructorError),
}


@needs_libyaml
@pytest.mark.parametrize("name", sorted(DOCUMENTS))
def test_both_loaders_read_a_document_alike(name):
    text, expected = DOCUMENTS[name]
    c, py = both(lambda loader: list(yaml.load_all(text, Loader=loader)))
    assert c == py
    if isinstance(c, list):
        assert repr(c) == repr(py)
    if expected is not None:
        assert py == expected


def loader_lines():
    return [line for line in RECORDER.render_lines() if line.startswith("simon_yaml_documents_total")]


@pytest.fixture
def counted():
    RECORDER.reset()
    yield loader_lines
    RECORDER.reset()


@pytest.fixture
def no_libyaml(monkeypatch):
    """The module's choice as a PyYAML without libyaml would have made it at
    import: `_pick_yaml_loader` asked again with the library saying so."""
    monkeypatch.setattr(yaml, "__with_libyaml__", False)
    loader, name = expand._pick_yaml_loader()
    monkeypatch.setattr(expand, "_YAML_LOADER", loader)
    monkeypatch.setattr(expand, "_YAML_LOADER_NAME", name)


def test_the_choice_follows_what_the_library_says_of_itself(monkeypatch):
    assert expand._pick_yaml_loader() == (
        (yaml.CSafeLoader, "c") if yaml.__with_libyaml__ else (yaml.SafeLoader, "python")
    )
    assert (expand._YAML_LOADER, expand._YAML_LOADER_NAME) == expand._pick_yaml_loader()
    monkeypatch.setattr(yaml, "__with_libyaml__", False)
    assert expand._pick_yaml_loader() == (yaml.SafeLoader, "python")
    monkeypatch.delattr(yaml, "__with_libyaml__")  # a PyYAML older than the attribute
    assert expand._pick_yaml_loader() == (yaml.SafeLoader, "python")


CLUSTER = os.path.join(REPO, "example", "cluster", "demo")
RENDERED = ["kind: A\n---\nkind: B\n---\n- skipped\n", "---\n", "kind: C\n"]


@needs_libyaml
def test_documents_are_counted_under_the_c_loader(counted):
    docs = expand.load_yaml_objects(CLUSTER)
    assert len(docs) == 10 and [d["kind"] for d in docs[:3]] == ["Deployment", "DaemonSet", "DaemonSet"]
    assert counted() == ['simon_yaml_documents_total{loader="c"} 10']
    assert expand.decode_yaml_strings(RENDERED) == [{"kind": "A"}, {"kind": "B"}, {"kind": "C"}]
    # what the parser yielded, the list it skips and the empty document too
    assert counted() == ['simon_yaml_documents_total{loader="c"} 15']


def test_without_libyaml_the_python_loader_reads_the_same_documents(counted, no_libyaml, monkeypatch):
    assert expand._YAML_LOADER is yaml.SafeLoader
    # the C loader must not be reached for at all
    monkeypatch.setattr(yaml, "CSafeLoader", None, raising=False)
    from_python = expand.load_yaml_objects(CLUSTER), expand.decode_yaml_strings(RENDERED)
    assert counted() == ['simon_yaml_documents_total{loader="python"} 15']
    monkeypatch.undo()  # the fixture's patches too: back to the build's own loader
    assert from_python == (expand.load_yaml_objects(CLUSTER), expand.decode_yaml_strings(RENDERED))
    assert repr(from_python[0]) == repr(expand.load_yaml_objects(CLUSTER))


@pytest.mark.parametrize("hidden", [False, True], ids=["build_loader", "python_loader"])
def test_a_malformed_file_raises_yaml_error(tmp_path, request, hidden):
    if hidden:
        request.getfixturevalue("no_libyaml")
    (tmp_path / "a-good.yaml").write_text("kind: Node\n")
    (tmp_path / "b-bad.yaml").write_text("kind: Node\nmetadata:\n  labels: [a, b\n")
    (tmp_path / "notes.txt").write_text("{{ not yaml, not read }}")
    with pytest.raises(yaml.YAMLError):
        expand.load_yaml_objects(str(tmp_path))
    with pytest.raises(yaml.YAMLError):
        expand.decode_yaml_strings(["kind: A\n", "a: [1\n"])
    os.remove(tmp_path / "b-bad.yaml")
    assert expand.load_yaml_objects(str(tmp_path)) == [{"kind": "Node"}]


def test_files_are_read_in_sorted_order_and_only_mappings_kept(tmp_path):
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / "z.yml").write_text("name: sub-z\n")
    (tmp_path / "b.yaml").write_text("name: b1\n---\n- a list\n---\nname: b2\n---\n---\nplain\n")
    (tmp_path / "a.yaml").write_text("\ufeffname: a\r\n")
    (tmp_path / "c.json").write_text('{"name": "not read"}')
    assert [d["name"] for d in expand.load_yaml_objects(str(tmp_path))] == ["a", "b1", "b2", "sub-z"]
    assert expand.load_yaml_objects(str(tmp_path / "b.yaml")) == [{"name": "b1"}, {"name": "b2"}]


NEWNODE = os.path.join(REPO, "example", "newnode", "demo")


def test_a_traced_load_opens_a_parse_span_per_input_and_marks_no_other_span(counted):
    tr = tracing.start_trace("apply", force=True)
    with tracing.trace_scope(tr):
        expand.load_yaml_objects(CLUSTER, "cluster")
        with tracing.span("inner") as sp:
            expand.decode_yaml_strings(RENDERED, "app:rendered")
        expand.load_cluster_from_dir(NEWNODE, "new_node")
    tr.finish()
    assert tr.root.attrs == {} and sp.attrs == {}
    assert [(s.name, s.attrs.get("input")) for s in tr.walk()] == [
        ("apply", None), ("load.parse", "cluster"), ("inner", None), ("load.parse", "app:rendered"),
        ("load.parse", "new_node"), ("load.objects", "new_node")]
    name = expand._YAML_LOADER_NAME
    parses = [s.attrs for s in tr.walk() if s.name == "load.parse"]
    cluster_bytes = sum(os.path.getsize(p) for p in expand.yaml_files_in_dir(CLUSTER) if p.endswith(".yaml"))
    assert parses[0] == {"input": "cluster", "files": 10, "bytes": cluster_bytes, "documents": 10, "yaml_loader": name}
    # what the parser yielded, the list it skips and the empty document too
    assert parses[1] == {"input": "app:rendered", "files": 3, "bytes": sum(len(s) for s in RENDERED),
                         "documents": 5, "yaml_loader": name}
    assert parses[2]["documents"] == 1
    assert tr.root.children[-1].attrs == {"input": "new_node", "objects": 1, "skipped": 0}
    # with no trace ambient there is no span, and the counter still counts
    expand.load_yaml_objects(CLUSTER)
    assert counted() == [f'simon_yaml_documents_total{{loader="{name}"}} 26']


@pytest.mark.parametrize("hidden", [False, True], ids=["build_loader", "python_loader"])
def test_a_traced_plan_opens_with_load_and_its_parses_count_every_document(tmp_path, counted, request, hidden):
    """The root's first child is `load`, one `load.parse` and one
    `load.objects` per input inside it; the report's tables are its children."""
    from opensim_tpu.chart.render import process_chart
    from opensim_tpu.planner.apply import Applier, Options

    if hidden:
        request.getfixturevalue("no_libyaml")
    tr = tracing.start_trace("apply", force=True)
    with tracing.trace_scope(tr):
        rc = Applier(Options(simon_config=os.path.join(REPO, "example", "simon-config.yaml"),
                             output_file=str(tmp_path / "report.txt"), report_pods=True)).run()
    tr.finish()
    assert rc == 0
    load = tr.root.children[0]
    inputs = ["cluster", "app:obs", "app:simple", "new_node"]
    assert load.name == "load"
    assert [(c.name, c.attrs["input"]) for c in load.children] == [
        (kind, i) for i in inputs for kind in ("load.parse", "load.objects")]

    def held(directory):
        total = 0
        for p in expand.yaml_files_in_dir(os.path.join(REPO, "example", directory)):
            if p.endswith((".yaml", ".yml")):
                with open(p) as f:
                    total += len(list(yaml.safe_load_all(f)))
        return total

    chart = sum(len(list(yaml.safe_load_all(s)))
                for s in process_chart("obs", os.path.join(REPO, "example", "application", "charts", "obs-stack")))
    expected = held("cluster/demo") + held("application/simple") + held("newnode/demo") + chart
    name = "python" if hidden else expand._pick_yaml_loader()[1]
    parses = [c.attrs for c in load.children if c.name == "load.parse"]
    assert {a["yaml_loader"] for a in parses} == {name}
    assert sum(a["documents"] for a in parses) == load.attrs["documents"] == expected
    assert load.attrs["inputs"] == 4 and load.attrs["bytes"] == sum(a["bytes"] for a in parses)
    assert not any(k.startswith("yaml_") for k in tr.root.attrs)
    assert counted() == [f'simon_yaml_documents_total{{loader="{name}"}} {expected}']
    (report,) = [c for c in tr.root.children if c.name == "report"]
    assert [c.name for c in report.children] == ["report.nodes", "report.pods", "report.apps"]
    assert report.children[-1].attrs == {"apps": 2}
