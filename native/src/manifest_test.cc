// Exit-code unit test for the manifest parser (the exporter<->runtime
// contract), in the reference's standalone-binary test style
// (/root/reference/src/quants-test.cpp pattern): writes a synthetic
// manifest to a temp dir, parses it, asserts every field — including the
// optional loop/prefill program sections and the warn-don't-abort handling
// of unknown keys a newer exporter may add.
#include <cassert>
#include <cstdio>
#include <cstdlib>
#include <fstream>
#include <string>

#include "manifest.h"

namespace {

std::string WriteTempManifest() {
  char tmpl[] = "/tmp/dllama_manifest_test_XXXXXX";
  const char* dir = mkdtemp(tmpl);
  assert(dir != nullptr);
  std::ofstream f(std::string(dir) + "/manifest.txt");
  f << "dllama_native 1\n"
       "model tiny\n"
       "vocab_size 96\n"
       "seq_len 32\n"
       "plugin /opt/plugins/libtpu.so\n"
       "option i num_chips 1\n"
       "option s pool_mode solo\n"
       "option b enable_thing 1\n"
       "weights_file weights.bin\n"
       "mlir_file model.mlir\n"
       "compile_options_file compile_options.pb\n"
       "loop_mlir_file model_loop.mlir\n"
       "loop_steps 32\n"
       "prefill_mlir_file model_prefill.mlir\n"
       "prefill_bucket 32\n"
       "prefill_executable_file executable_prefill.bin\n"
       "tp_mlir_file model_tp2.mlir\n"      // unknown to this parser:
       "tp_degree 2\n"                      // must warn, not abort
       "input w.0 weight f32 0 64 2 4 4\n"
       "input cache.k cache f32 -1 128 3 2 4 4\n"
       "input cache.v cache f32 -1 128 3 2 4 4\n"
       "input token token i32 -1 4 1 1\n"
       "input pos pos i32 -1 4 0\n"
       "output logits logits f32 1 96\n"
       "output cache.k cache f32 3 2 4 4\n"
       "output cache.v cache f32 3 2 4 4\n";
  f.close();
  return dir;
}

}  // namespace

int main() {
  const std::string dir = WriteTempManifest();
  dllama::Manifest m = dllama::LoadManifest(dir);

  assert(m.version == 1);
  assert(m.model_name == "tiny");
  assert(m.vocab_size == 96);
  assert(m.seq_len == 32);
  assert(m.plugin_path == "/opt/plugins/libtpu.so");
  assert(m.options.size() == 3);
  assert(m.options[0].type == 'i' && m.options[0].name == "num_chips" &&
         m.options[0].value == "1");
  assert(m.options[2].type == 'b' && m.options[2].value == "1");

  assert(m.weights_file == "weights.bin");
  assert(m.mlir_file == "model.mlir");
  assert(m.loop_mlir_file == "model_loop.mlir" && m.loop_steps == 32);
  assert(m.prefill_mlir_file == "model_prefill.mlir");
  assert(m.prefill_bucket == 32);
  assert(m.prefill_executable_file == "executable_prefill.bin");
  assert(m.executable_file.empty());  // optional and absent

  assert(m.inputs.size() == 5);
  assert(m.inputs[0].kind == dllama::ArgKind::kWeight &&
         m.inputs[0].offset == 0 && m.inputs[0].nbytes == 64 &&
         m.inputs[0].dims.size() == 2 && m.inputs[0].dims[1] == 4);
  assert(m.inputs[1].kind == dllama::ArgKind::kCache &&
         m.inputs[1].dims.size() == 3);
  assert(m.inputs[3].kind == dllama::ArgKind::kToken);
  assert(m.inputs[4].kind == dllama::ArgKind::kPos &&
         m.inputs[4].dims.empty());

  assert(m.outputs.size() == 3);
  assert(m.outputs[0].kind == "logits" && m.outputs[0].dims.size() == 1 &&
         m.outputs[0].dims[0] == 96);

  assert(m.path("x.bin") == dir + "/x.bin");

  // a manifest without inputs/outputs must be rejected
  char tmpl2[] = "/tmp/dllama_manifest_test_XXXXXX";
  const char* dir2 = mkdtemp(tmpl2);
  assert(dir2 != nullptr);
  {
    std::ofstream f2(std::string(dir2) + "/manifest.txt");
    f2 << "dllama_native 1\n";
  }
  bool threw = false;
  try {
    dllama::LoadManifest(dir2);
  } catch (const std::exception&) {
    threw = true;
  }
  assert(threw);

  std::printf("manifest_test: OK\n");
  return 0;
}
